"""Model FLOP/s utilisation of the traced window: the operations the
forward and backward passes require for a token (cost model; nothing
recomputed is counted) x tokens a second, over the chips' peak."""
from benchmarks.trace import share_pct


def read(sources, args):
    traced, shape = sources.get("traced"), sources.get("shape")
    if not traced or not shape or not traced.get("tokens"):
        return None
    if not sources.get("peak"):      # a rehearsal: no chip, no peak
        return None
    per_token = sources["cost_model"].train_flops_per_token(
        sources["model"], shape["seq"])
    rate = traced["tokens"] / (traced["t1"] - traced["t0"])
    peak = sources["peak"]["flops_per_s"] * sources.get("chips", 1)
    return share_pct(per_token * rate, peak, "mfu")
