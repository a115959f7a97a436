#!/usr/bin/env python3
"""The quickest proof that the framework still starts on the chip.

Drives the two main paths once, through the entry points a user calls,
at the full published width of the one LLM family the repo supports
(``LlamaConfig`` defaults = Llama-7B: D=4096, 32 heads x 128, F=11008,
V=32000). Depth is cut, never a width; weights are random from ``--seed``.

    python chip_smoke.py              one chip: sync probe, serving, a
                                      window + global + expert model,
                                      training
    python chip_smoke.py --chips 4    four chips, and ONLY the paths that
                                      exist across chips: tensor-parallel
                                      serving and the sharded Trainer, each
                                      against its one-chip comparison
    python chip_smoke.py --tiny       rehearsal size for JAX_PLATFORMS=cpu
                                      (never a chip run; names the CPU)

The script sets no platform. Unless ``--tiny`` was given it refuses to
start without a TPU. One process touches JAX; nothing is spawned. Every
phase prints one JSON line; any phase that fails raises, so the exit code
is non-zero and the last line is never printed. The LAST stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

What is held to what:
- serving: every request finishes; greedy tokens against dense
  ``generate()`` over the ENGINE's tree (q/k/v as its one fused leaf,
  so the reference's products have the engine's shapes) — bf16 on the
  chip is not
  bit-parity with a differently-shaped program, so the FIRST token of
  every request must be equal and the share of equal tokens over all
  positions is reported and must reach ``TOKEN_MATCH_FLOOR``;
  0 retrace warnings; the compiled decode program holds the Pallas
  custom call of every route dispatch reported;
- training: >= 3 steps on one repeated seeded batch, loss finite and
  falling; flash-attention and fused-CE custom calls in the compiled
  step; the Trainer's fused-optimizer decision printed;
- four chips: TP=4 tokens against the one-chip engine under the same
  rule, a non-empty collective record, KV pools resident on 4 devices;
  sharded-Trainer losses against the one-device Trainer's within
  ``LOSS_RTOL``, parameters resident on 4 devices.
"""
import argparse
import dataclasses
import gc
import json
import re
import sys
import time

#: share of positions at which the engine's greedy tokens must equal the
#: reference's (after an equal first token): one bf16 near-tie flips a
#: token and every later position of that request with it
TOKEN_MATCH_FLOOR = 0.5
#: sharded vs one-device loss, same batch and step: bf16 matmuls reduce
#: in another order across shards, and the one-device step runs the
#: Pallas kernels where the sharded one runs the compositions
LOSS_RTOL = 2e-2
#: small enough that a few steps on one repeated batch stay where two
#: numerically different programs track each other (at the Trainer's
#: default 3e-4 one AdamW step over 0.87 B parameters memorises the
#: 4096 tokens: 11.18 -> 0.20 on the chip)
LEARNING_RATE = 1e-5


def say(phase, **kv):
    print(json.dumps({"phase": phase, **kv}), flush=True)


def check(ok, what):
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


class CompileClock:
    """Seconds JAX spent in the backend compiler (or fetching from the
    persistent cache), the cache's hits, and the entries it wrote (a
    miss is recorded when the entry is written: compiles over JAX's
    one-second floor), from ``jax.monitoring`` — what tells a cold run
    from a warm one."""

    def __init__(self):
        from jax import monitoring
        self.secs = 0.0
        self.hits = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._dur)
        monitoring.register_event_listener(self._evt)

    def _dur(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _evt(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def lap(self):
        out = {"compile_s": round(self.secs, 2), "cache_hits": self.hits,
               "cache_writes": self.misses}
        self.secs, self.hits, self.misses = 0.0, 0, 0
        return out


def kernels_in(hlo_text):
    from paddle_tpu.ops.pallas._util import compiled_kernel_counts
    return compiled_kernel_counts(hlo_text)


def peak_hbm_gib(devices):
    """``peak_bytes_in_use`` of each device's allocator since the
    process began (None where the backend reports none, as the CPU). A
    program's own temporaries are not in it: see ``step_program_gib``."""
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    return [round(p / 2 ** 30, 2) if p is not None else None
            for p in peaks]


def step_program_gib(tr):
    """What the compiled train step needs on each device by the
    compiler's own count (arguments + outputs - aliased + temporaries)."""
    hbm = tr.metrics()["hbm"] or {}
    return round(hbm["total_bytes"] / 2 ** 30, 2) if hbm else None


def token_match(got, want):
    """(every first token equal, share of equal positions)."""
    first = all(g[0] == w[0] for g, w in zip(got, want))
    eq = sum(int(a == b) for g, w in zip(got, want) for a, b in zip(g, w))
    return first, eq / sum(len(w) for w in want)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_sync(jax, jnp, tiny):
    """Does ``block_until_ready`` wait for the device? Time one chain
    of matmuls three ways: dispatch alone, dispatch + block_until_ready,
    dispatch + a host read of one element."""
    n, reps = (256, 4) if tiny else (4096, 64)

    @jax.jit
    def chain(x):
        for _ in range(reps):
            x = jnp.tanh(x @ x) * 0.5
        return x

    x = jnp.ones((n, n), jnp.bfloat16)
    float(chain(x)[0, 0])                    # compile + warm both forms
    t0 = time.perf_counter()
    y = chain(x)
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready(y)
    t_bur = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(chain(x)[0, 0])
    t_host = time.perf_counter() - t0
    say("sync", matmul_chain=f"{reps} x ({n}x{n})",
        dispatch_ms=round(t_dispatch * 1e3, 3),
        block_until_ready_ms=round(t_bur * 1e3, 3),
        host_read_ms=round(t_host * 1e3, 3),
        block_until_ready_synchronises=bool(t_bur > 0.5 * t_host))
    # at the rehearsal size the work is shorter than the clock's noise
    check(tiny or t_bur > 0.5 * t_host,
          "block_until_ready returned long before a host read of the "
          "same work did: it does not wait for the device here")


def describe(cfg, full_depth, tiny):
    """What ran: the model, its widths, and the cut of depth."""
    return {
        "model": "LLAMA_TINY (rehearsal, not a chip size)" if tiny
        else "LLAMA_7B widths, random weights",
        "widths": {"D": cfg.hidden_size, "H": cfg.num_attention_heads,
                   "KV": cfg.num_key_value_heads, "hd": cfg.head_dim,
                   "F": cfg.intermediate_size, "V": cfg.vocab_size,
                   "dtype": cfg.dtype.__name__},
        "reduced": {"layers": f"{cfg.num_hidden_layers} of {full_depth}"}}


def serving_cfg(llama, tiny):
    """(config, published depth): 8 of Llama-7B's 32 layers."""
    if tiny:
        return llama.LLAMA_TINY, llama.LLAMA_TINY.num_hidden_layers
    return (dataclasses.replace(llama.LLAMA_7B, num_hidden_layers=8),
            llama.LLAMA_7B.num_hidden_layers)


def engine_options(tiny):
    """The engine's defaults (paged pools, fused routes on auto) with
    the prefix cache on and the retrace watchdog's harness; the
    rehearsal shrinks the pools, pages and buckets to its widths."""
    small = {"prefill_buckets": (8, 32), "block_size": 8} if tiny else {}
    return dict(prefix_cache=True, observability=True,
                max_seq_len=64 if tiny else 512, **small)


def make_prompts(np, seed, vocab, tiny):
    """Mixed lengths: two under the small bucket, one under the large,
    one LONGER than the largest prefill bucket (chunked), and a pair
    sharing a 3-page prefix (the second is sent once the first has
    finished, so the radix cache can serve it)."""
    rng = np.random.RandomState(seed)
    lens = (6, 6, 20, 40) if tiny else (24, 24, 100, 200)
    pre, tail = (16, 8) if tiny else (48, 32)
    first = [rng.randint(0, vocab, (n,)).astype(np.int32) for n in lens]
    prefix = rng.randint(0, vocab, (pre,)).astype(np.int32)
    pair = [np.concatenate([prefix, rng.randint(0, vocab, (tail,))
                            .astype(np.int32)]) for _ in range(2)]
    return first + pair[:1], pair[1:]


def run_engine(eng, waves, gen):
    """Submit each wave, drain it, arm the retrace watchdog after the
    first (every bucket and the decode program have compiled by then)."""
    reqs = []
    for i, wave in enumerate(waves):
        reqs += [eng.submit(p, gen) for p in wave]
        eng.drain()
        if i == 0:
            eng.reset_metrics()
    return reqs


def compiled_decode_kernels(eng):
    (spec,) = [s for s in eng.program_specs(register=False)
               if s.name.startswith("serving_decode")]
    return kernels_in(spec.fn.lower(*spec.args).compile().as_text())


def phase_serving(jax, np, args, clock):
    from paddle_tpu.inference import GenerationConfig, ServingEngine
    from paddle_tpu.inference.generation import generate
    from paddle_tpu.models import llama

    cfg, full_depth = serving_cfg(llama, args.tiny)
    n_new = 8 if args.tiny else 16
    params = llama.init_params(cfg, jax.random.key(args.seed))
    eng = ServingEngine(params, cfg, **engine_options(args.tiny))
    gen = GenerationConfig(max_new_tokens=n_new, greedy=True)
    waves = make_prompts(np, args.seed, cfg.vocab_size, args.tiny)
    t0 = time.perf_counter()
    reqs = run_engine(eng, waves, gen)
    wall = time.perf_counter() - t0
    check(all(r.done and len(r.tokens) == n_new for r in reqs),
          "a request did not finish with all its tokens")
    m = eng.metrics()
    serve_clock = clock.lap()

    # over the engine's tree: three products in the reference and one
    # in the engine round their bf16 columns in different tilings, and
    # a near-tie of random weights then parts a request for good
    ref = [np.asarray(generate(eng.params, r.prompt[None], cfg, gen,
                               seed=args.seed))[0, r.prompt.size:]
           for r in reqs]
    first_eq, share = token_match([r.tokens for r in reqs], ref)
    found = compiled_decode_kernels(eng)
    want = set(m["decode_variant"]["operands"])   # its Pallas launches
    say("serving", **describe(cfg, full_depth, args.tiny),
        requests=len(reqs), finished=sum(r.done for r in reqs),
        prompt_lens=[int(r.prompt.size) for r in reqs],
        prefill_buckets=list(eng.buckets), new_tokens=n_new,
        first_token_equal=first_eq, token_match_share=round(share, 4),
        token_match_floor=TOKEN_MATCH_FLOOR,
        tokens=[list(map(int, r.tokens[:4])) for r in reqs],
        decode_variant=m["decode_variant"],
        prefill_variant=m["prefill_variant"],
        decode_traces=m["decode_traces"],
        prefill_traces=m["prefill_traces"],
        retrace_warnings=m["retrace_warnings"],
        prefix_cache={k: m["prefix_cache"][k]
                      for k in ("hits", "misses", "tokens_skipped")},
        decode_kernels_compiled=found, wall_s=round(wall, 2),
        **serve_clock, reference=clock.lap(),
        peak_hbm_gib=peak_hbm_gib(jax.devices()[:1]))
    check(first_eq, "a request's first greedy token differs from dense "
                    "generate() over the same params")
    check(share >= TOKEN_MATCH_FLOOR,
          f"token match share {share:.3f} < {TOKEN_MATCH_FLOOR}")
    check(m["retrace_warnings"] == 0, "the engine retraced in steady state")
    check(m["decode_traces"] == 1, "more than one decode program traced")
    check(want <= set(found),
          f"dispatch reported {m['decode_variant']} but the compiled "
          f"decode program holds {found}")


def phase_pattern(jax, jnp, np, args, clock):
    """A model run by its layer pattern with two page lifetimes: one
    period of Mellum 2's published pattern (3 sliding-window layers, 1
    full-attention layer with YaRN, a 64-expert top-8 layer in each) at
    its published widths, a prompt LONGER than the window beside a short
    one, so that chunks and decode steps give window pages back. Greedy
    tokens against the model file's full-sequence ``forward``."""
    from paddle_tpu.inference import GenerationConfig, ServingEngine
    from paddle_tpu.models import mellum

    if args.tiny:
        cfg = dataclasses.replace(mellum.MELLUM_TINY, num_hidden_layers=4)
        lens, opts = (30, 5), dict(capacity=2, block_size=4,
                                   max_seq_len=64, prefill_buckets=(8, 16))
    else:
        cfg = mellum.MellumConfig(num_hidden_layers=4)
        lens, opts = (1100, 40), dict(capacity=4, block_size=16,
                                      max_seq_len=1536,
                                      prefill_buckets=(128, 512))
    n_new = 8
    params = mellum.init_params(cfg, jax.random.key(args.seed))
    eng = ServingEngine(params, cfg, **opts)
    rng = np.random.RandomState(args.seed)
    gen = GenerationConfig(max_new_tokens=n_new, greedy=True)
    reqs = [eng.submit(rng.randint(0, cfg.vocab_size, (n,))
                       .astype(np.int32), gen) for n in lens]
    eng.drain()
    eng.mgr.check()
    check(all(r.done and len(r.tokens) == n_new for r in reqs),
          "a request did not finish with all its tokens")
    m = eng.metrics()
    ref = []
    for r in reqs:          # teacher-forced on the served tokens
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1],
                                                   np.int32)])
        logits = mellum.forward(params, jnp.asarray(seq), cfg)
        ref.append(np.asarray(jnp.argmax(
            logits[r.prompt.size - 1:], axis=-1)))
    first_eq, share = token_match([r.tokens for r in reqs], ref)
    found = compiled_decode_kernels(eng)
    want = set(m["decode_variant"]["operands"])
    released = eng.counters["window_pages_released"]
    what = describe(cfg, 28, args.tiny)
    what["model"] = ("MELLUM_TINY (rehearsal, not a chip size)" if args.tiny
                     else "Mellum2-12B-A2.5B widths, random weights")
    what["widths"]["F"] = cfg.moe_intermediate_size     # an expert's
    say("pattern", **what, pattern=list(cfg.pattern),
        experts=cfg.num_experts, prompt_lens=list(lens),
        first_token_equal=first_eq, token_match_share=round(share, 4),
        window_pages_released=released,
        window=m["pattern"]["window"],
        decode_variant=m["decode_variant"],
        decode_traces=m["decode_traces"],
        decode_kernels_compiled=found, **clock.lap(),
        peak_hbm_gib=peak_hbm_gib(jax.devices()[:1]))
    check(first_eq, "a request's first greedy token differs from the "
                    "full-sequence forward over the same params")
    check(share >= TOKEN_MATCH_FLOOR,
          f"token match share {share:.3f} < {TOKEN_MATCH_FLOOR}")
    check(released > 0, "no window page went back: the long prompt "
                        "never left its window")
    check(m["decode_traces"] == 1, "more than one decode program traced")
    check(want <= set(found),
          f"dispatch reported {m['decode_variant']} but the compiled "
          f"decode program holds {found}")


def training_setup(jax, jnp, np, args, mesh_cfg, devices):
    from paddle_tpu.distributed.trainer import Trainer, make_mesh
    from paddle_tpu.models import llama

    if args.tiny:
        cfg, batch, seq = llama.LLAMA_TINY, 2, 64
    else:
        # bench.py's full-width rung "1.07B-h4096" is batch 2 x seq 2048
        # x 4 layers with bf16 moments; compiled for the v5e its one-chip
        # step needs 16.56 GB of 15.75 (the fused optimizer's flat fp32
        # gradient is 4 GB), so depth is cut to 3: 13.5 GB
        batch, seq = 2, 2048
        cfg = dataclasses.replace(llama.LLAMA_7B, num_hidden_layers=3,
                                  max_position_embeddings=seq)
    mesh = make_mesh(mesh_cfg, devices=devices)
    tr = Trainer(lambda p, t, l: llama.loss_fn(p, t, l, cfg), mesh,
                 llama.param_shardings(mesh, cfg), lr=LEARNING_RATE,
                 moment_dtype=jnp.bfloat16, observability=True)
    state = tr.init_state(llama.init_params(cfg,
                                            jax.random.key(args.seed)))
    rng = np.random.RandomState(args.seed)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                       jnp.int32)
    what = describe(cfg, cfg.num_hidden_layers if args.tiny
                    else llama.LLAMA_7B.num_hidden_layers, args.tiny)
    return what, tr, state, toks, jnp.roll(toks, -1, axis=1)


def train_steps(tr, state, toks, labels, n):
    losses = []
    for _ in range(n):
        state, m = tr.step(state, toks, labels)
        losses.append(float(m["loss"]))
    return state, losses


def trainer_kernels(tr):
    (compiled,) = tr._compiled_cache.values()
    return kernels_in(compiled.as_text())


def phase_training(jax, jnp, np, args, clock):
    from paddle_tpu.distributed.trainer import MeshConfig

    what, tr, state, toks, labels = training_setup(
        jax, jnp, np, args, MeshConfig(), jax.devices()[:1])
    t0 = time.perf_counter()
    state, losses = train_steps(tr, state, toks, labels, 4)
    wall = time.perf_counter() - t0
    found = trainer_kernels(tr)
    latency = tr.metrics()["latency"]
    n_params = sum(v.size for v in jax.tree_util.tree_leaves(state.params))
    say("training", **what, rung=None if args.tiny else
        "bench.py 1.07B-h4096 (batch 2 x seq 2048, bf16 moments), 3 "
        "layers for its 4: the one-chip step of 4 does not fit 16 GB",
        params=int(n_params), batch=list(toks.shape), steps=len(losses),
        losses=[round(x, 4) for x in losses],
        learning_rate=LEARNING_RATE,
        fused_optimizer=bool(tr._fused),
        optimizer_variant=tr.metrics()["optimizer_variant"],
        step_kernels_compiled=found,
        # host share of a step: stage + dispatch against the wait for
        # the device (a blocking transfer in the step plumbing shows here)
        step_phase_ms_mean={k: latency[k]["mean"] for k in
                            ("stage_ms", "dispatch_ms", "sync_ms")},
        wall_s=round(wall, 2), **clock.lap(),
        step_program_gib=step_program_gib(tr),
        peak_hbm_gib=peak_hbm_gib(jax.devices()[:1]))
    check(all(np.isfinite(losses)), f"non-finite loss {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    if jax.devices()[0].platform == "tpu":
        check(tr._fused, "the llama tree (bf16 weights + fp32 norms) on a "
                         "one-chip mesh did not take the fused AdamW")
        want = {"flash_attention_fwd", "flash_attention_bwd_dq",
                "flash_attention_bwd_dkv", "linear_ce_fwd",
                "linear_ce_bwd_dx", "linear_ce_bwd_dh", "fused_adamw"}
        picked = tr.optimizer_variant
        check(picked["variant"] == "pallas_fused" and picked["block"],
              f"the step's optimizer is not the Pallas launch: {picked}")
        check(want <= set(found),
              f"the compiled train step lacks {sorted(want - set(found))}")


def phase_serving_4(jax, np, args, clock):
    """TP=4 engine against the one-chip engine, same prompts."""
    from paddle_tpu.inference import (GenerationConfig, ServingEngine,
                                      ServingMesh)
    from paddle_tpu.models import llama

    cfg, full_depth = serving_cfg(llama, args.tiny)
    if args.tiny:        # LLAMA_TINY has 2 KV heads; tp=4 needs 4
        cfg = dataclasses.replace(cfg, num_key_value_heads=4)
    n_new = 8 if args.tiny else 16
    params = llama.init_params(cfg, jax.random.key(args.seed))
    kw = engine_options(args.tiny)
    gen = GenerationConfig(max_new_tokens=n_new, greedy=True)
    waves = make_prompts(np, args.seed, cfg.vocab_size, args.tiny)

    one = ServingEngine(params, cfg, **kw)
    ref = [list(r.tokens) for r in run_engine(one, waves, gen)]
    del one
    gc.collect()
    eng = ServingEngine(params, cfg, mesh=ServingMesh.make(tp=4), **kw)
    reqs = run_engine(eng, waves, gen)
    check(all(r.done and len(r.tokens) == n_new for r in reqs),
          "a request did not finish with all its tokens")
    m = eng.metrics()
    first_eq, share = token_match([r.tokens for r in reqs], ref)
    pool_devs = sorted(d.id for d in eng._k_pools.sharding.device_set)
    say("serving_tp4", **describe(cfg, full_depth, args.tiny),
        mesh=m["mesh"], requests=len(reqs),
        first_token_equal=first_eq, token_match_share=round(share, 4),
        token_match_floor=TOKEN_MATCH_FLOOR,
        decode_variant=m["decode_variant"],
        decode_traces=m["decode_traces"],
        retrace_warnings=m["retrace_warnings"],
        collectives=m["collectives"]["calls"],
        kv_pool_devices=pool_devs,
        kv_pool_shard_shape=list(
            eng._k_pools.addressable_shards[0].data.shape),
        decode_kernels_compiled=compiled_decode_kernels(eng),
        **clock.lap(), peak_hbm_gib=peak_hbm_gib(jax.devices()[:4]))
    check(first_eq, "a TP=4 first token differs from the one-chip engine")
    check(share >= TOKEN_MATCH_FLOOR,
          f"token match share {share:.3f} < {TOKEN_MATCH_FLOOR}")
    check(m["retrace_warnings"] == 0, "the engine retraced in steady state")
    check(m["collectives"]["calls"], "no collective was recorded")
    check(len(pool_devs) == 4, f"KV pools live on devices {pool_devs}")


def phase_training_4(jax, jnp, np, args, clock):
    """Three steps on fsdp=2 x tp=2 against the one-device Trainer."""
    from paddle_tpu.distributed.trainer import MeshConfig

    what, tr1, st1, toks, labels = training_setup(
        jax, jnp, np, args, MeshConfig(), jax.devices()[:1])
    _, ref = train_steps(tr1, st1, toks, labels, 3)
    del tr1, st1
    gc.collect()
    _, tr, state, toks, labels = training_setup(
        jax, jnp, np, args, MeshConfig(fsdp=2, tp=2), jax.devices()[:4])
    state, losses = train_steps(tr, state, toks, labels, 3)
    w = state.params["layers"]["gate_proj"]
    devs = sorted(d.id for d in w.sharding.device_set)
    hlo = next(iter(tr._compiled_cache.values())).as_text()
    colls = {c: len(re.findall(r"\b" + c + r"(?:-start)?\(", hlo))
             for c in ("all-reduce", "all-gather", "reduce-scatter")}
    say("training_4", **what,
        mesh={k: int(v) for k, v in tr.mesh.shape.items() if v > 1},
        losses=[round(x, 4) for x in losses],
        one_device_losses=[round(x, 4) for x in ref], loss_rtol=LOSS_RTOL,
        param_devices=devs,
        gate_proj_shard_shape=list(w.addressable_shards[0].data.shape),
        gate_proj_shape=list(w.shape), collectives_compiled=colls,
        step_kernels_compiled=kernels_in(hlo), **clock.lap(),
        step_program_gib=step_program_gib(tr),
        peak_hbm_gib=peak_hbm_gib(jax.devices()[:4]))
    check(all(np.isfinite(losses)), f"non-finite loss {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(np.allclose(losses, ref, rtol=LOSS_RTOL),
          f"sharded losses {losses} vs one-device {ref}")
    check(len(devs) == 4, f"parameters live on devices {devs}")
    check(sum(colls.values()) > 0, "no collective in the sharded step")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal size; never a chip run")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    if not args.tiny and d0.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {device} "
                 "(--tiny rehearses on the CPU)")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{device}")

    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu  # noqa: F401 — x64 mode + the compile cache
    from paddle_tpu.ops.pallas.autotune import GLOBAL_FLAGS

    # no autotune sweep and no winners table read: what runs is decided
    # by committed files only
    GLOBAL_FLAGS.set("kernel_autotune", False)
    clock = CompileClock()
    say("start", device=device, chips=args.chips, tiny=args.tiny,
        seed=args.seed, jax=jax.__version__,
        compile_cache=jax.config.jax_compilation_cache_dir)
    phases = ([lambda: phase_serving_4(jax, np, args, clock),
               lambda: phase_training_4(jax, jnp, np, args, clock)]
              if args.chips == 4 else
              [lambda: phase_sync(jax, jnp, args.tiny),
               lambda: phase_serving(jax, np, args, clock),
               lambda: phase_pattern(jax, jnp, np, args, clock),
               lambda: phase_training(jax, jnp, np, args, clock)])
    for phase in phases:
        phase()
        gc.collect()     # the next phase needs the device memory back
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
