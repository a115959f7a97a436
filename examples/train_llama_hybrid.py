"""Hybrid-parallel LLaMA training over a named mesh.

The reference wires Fleet process groups + NCCL by hand; here the
SAME hybrid topology is a `jax.sharding.Mesh` with named axes and the
Trainer's GSPMD shardings — XLA inserts the collectives. Includes the
round-5 perf stack: fused flat-state AdamW (mixed bf16/fp32 tree),
bf16 optimizer moments, gradient accumulation, device-prefetched
ingest — and the round-9 training observability: per-step phase
histograms (stage/dispatch/sync), compile telemetry with automatic
MFU, and a chrome trace you can open in Perfetto."""
import numpy as np

from _common import setup

jax = setup()

import jax.numpy as jnp                                   # noqa: E402
from paddle_tpu.distributed.trainer import (MeshConfig,   # noqa: E402
                                            Trainer, make_mesh)
from paddle_tpu.models.llama import (LlamaConfig,         # noqa: E402
                                     init_params, loss_fn,
                                     param_shardings)


def main():
    cfg = LlamaConfig(vocab_size=1024, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=4,
                      max_position_embeddings=128)
    mesh = make_mesh(MeshConfig(fsdp=2, sp=2, tp=2))   # 8 devices
    params = init_params(cfg, jax.random.PRNGKey(0))
    tr = Trainer(lambda p, t, l: loss_fn(p, t, l, cfg), mesh,
                 param_shardings(mesh, cfg), lr=3e-4,
                 accumulate_steps=1, moment_dtype=jnp.bfloat16,
                 observability=True)
    state = tr.init_state(params)

    rng = np.random.RandomState(0)

    def batches():
        while True:
            toks = rng.randint(0, 1024, (4, 128)).astype(np.int32)
            yield toks, np.roll(toks, -1, -1)

    it = iter(batches())
    # device prefetch: batch N+1's h2d overlaps step N's compute
    # (observability samples the staged-queue depth on each pull)
    pf = tr.prefetch((next(it) for _ in range(8)))
    for i, (toks, labels) in enumerate(pf):
        state, m = tr.step(state, toks, labels)
        print(f"step {i}: loss {float(m['loss']):.4f} "
              f"gnorm {float(m['grad_norm']):.3f}")

    # training telemetry: per-step phase split, compile wall time,
    # cost-analysis MFU, HBM breakdown
    tm = tr.metrics()
    st = tm["latency"]["step_ms"]
    print(f"steps={tm['steps']} tokens/s={tm['tokens_per_sec']:.0f} "
          f"step_ms p50={st['p50']} p99={st['p99']} "
          f"compiles={tm['compiles']}")
    if tm["mfu"]:
        print(f"mfu={tm['mfu']['mfu']} (flops/step/device="
              f"{tm['mfu']['flops_per_step_per_device']:.3g}, "
              f"peak={tm['mfu']['peak_source']})")
    tr.export_trace("train_trace.json")
    tr.write_timeline("train_timeline.jsonl")
    print("wrote train_trace.json + train_timeline.jsonl "
          "(tools/trace_summary.py --mode train)")


if __name__ == "__main__":
    main()
