"""Serving: ONE compiled generate program (prefill + scanned decode),
then the vLLM-style paged-KV loop, then the same loop on an int8
quantized cache (half the KV HBM -> 2x batch at the same footprint),
then mixed-arrival traffic through the continuous-batching
ServingEngine vs the static batch (head-of-line blocking demo) with
the OBSERVABILITY layer on (TTFT/TPOT/queue-wait percentiles, per-step
allocator gauges, chrome-trace + JSONL timeline export), and finally
the radix PREFIX CACHE: requests sharing a system prompt skip
prefilling the shared pages (copy-on-write KV page sharing)."""
import time

import numpy as np

from _common import setup

jax = setup()

import jax.numpy as jnp                                    # noqa: E402
from paddle_tpu.inference.generation import (              # noqa: E402
    GenerationConfig, generate, generate_paged)
from paddle_tpu.inference.serving import ServingEngine     # noqa: E402
from paddle_tpu.models.llama import (LlamaConfig,          # noqa: E402
                                     init_params)


def main():
    cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=4,
                      max_position_embeddings=160)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = jnp.asarray(
        np.random.RandomState(0).randint(0, 512, (2, 32)), jnp.int32)
    g = GenerationConfig(max_new_tokens=16, greedy=True)

    for name, fn in (
            ("dense-cache compiled generate",
             lambda: generate(params, prompts, cfg, g)),
            ("paged KV cache",
             lambda: generate_paged(params, prompts, cfg, g)),
            ("paged + int8 cache quant",
             lambda: generate_paged(params, prompts, cfg, g,
                                    cache_dtype="int8"))):
        np.asarray(fn())                # compile + drain warmup
        t0 = time.perf_counter()
        out = fn()
        np.asarray(out)                 # sync
        dt = time.perf_counter() - t0
        print(f"{name}: out {out.shape}, {dt * 1e3:.1f} ms "
              f"({out.shape[0] * g.max_new_tokens / dt:.1f} tok/s)")

    # -- mixed-arrival traffic: continuous batching vs static batch ----
    # 8 requests with staggered arrivals and mixed lengths. The static
    # batch can only start once ALL prompts are in and drains at the
    # slowest request; the engine admits each arrival immediately,
    # recycles finished slots, and reports per-request TTFT.
    rng = np.random.RandomState(1)
    arrivals = np.cumsum(rng.exponential(0.02, 8))
    reqs_spec = [(rng.randint(0, 512, (int(s),)).astype(np.int32),
                  GenerationConfig(max_new_tokens=int(n), greedy=True))
                 for s, n in zip(rng.randint(8, 33, 8),
                                 rng.randint(8, 17, 8))]
    eng = ServingEngine(params, cfg, capacity=4, block_size=16,
                        prefill_buckets=(16, 32), max_seq_len=96,
                        observability=True)
    for warm_len in (16, 32):        # compile warmup: both prefill
        eng.submit(np.zeros(warm_len, np.int32),  # buckets + decode
                   GenerationConfig(max_new_tokens=2, greedy=True))
    eng.drain()
    eng.reset_metrics()   # restart the stats window + arm the watchdog
    t0 = time.perf_counter()
    i = 0
    while i < len(reqs_spec) or not eng.idle:
        now = time.perf_counter() - t0
        while i < len(reqs_spec) and arrivals[i] <= now:
            eng.submit(*reqs_spec[i])
            i += 1
        if not eng.step() and i < len(reqs_spec):
            time.sleep(0.001)
    m = eng.metrics()
    print(f"ServingEngine mixed arrivals: {m['tokens_generated']} toks, "
          f"{m['tokens_per_sec']:.1f} tok/s, "
          f"TTFT mean {m['ttft_ms_mean']:.1f} ms, "
          f"slot util {m['slot_utilization']:.2f}, traces: "
          f"decode={m['decode_traces']} prefill={m['prefill_traces']}")
    # the observability layer: full latency distributions, allocator
    # gauges sampled every step, and a scrub-able chrome trace
    lat = m["latency"]
    print("  latency p50/p95/p99 ms: "
          f"ttft {lat['ttft_ms']['p50']}/{lat['ttft_ms']['p95']}"
          f"/{lat['ttft_ms']['p99']}, "
          f"queue wait {lat['queue_wait_ms']['p50']}"
          f"/{lat['queue_wait_ms']['p95']}"
          f"/{lat['queue_wait_ms']['p99']}, "
          f"decode step {lat['decode_step_ms']['p50']}"
          f"/{lat['decode_step_ms']['p95']}"
          f"/{lat['decode_step_ms']['p99']}")
    print(f"  gauges: pages free last={m['gauges']['pages_free']['last']}"
          f" min={m['gauges']['pages_free']['min']}, "
          f"retrace warnings={m['retrace_warnings']}")
    trace = eng.export_trace("serve_paged_trace.json")
    jsonl = eng.write_timeline("serve_paged_timeline.jsonl")
    print(f"  chrome trace -> {trace} (open in Perfetto), "
          f"timeline -> {jsonl} "
          f"(python tools/trace_summary.py {jsonl})")

    # -- radix prefix cache: shared system prompt ----------------------
    # 6 requests = one 48-token system prompt + distinct 8-token user
    # tails. With prefix_cache=True the first request prefills the
    # shared pages once; every later request longest-prefix-matches at
    # admission, appends the shared pages to its block table (the
    # partially-filled tail page arrives as a copy-on-write fork) and
    # prefills only its un-cached suffix. Greedy outputs stay
    # bit-identical to the cold path.
    sys_prompt = rng.randint(0, 512, (48,)).astype(np.int32)
    eng = ServingEngine(params, cfg, capacity=4, block_size=16,
                        prefill_buckets=(16, 64), max_seq_len=96,
                        prefix_cache=True)
    for _ in range(6):
        tail = rng.randint(0, 512, (8,)).astype(np.int32)
        eng.submit(np.concatenate([sys_prompt, tail]),
                   GenerationConfig(max_new_tokens=8, greedy=True))
        eng.step()      # staggered arrivals: the first request's
        #                 prefill indexes the shared pages, so every
        #                 LATER arrival hits while it still decodes
    eng.drain()
    m = eng.metrics()
    pc = m["prefix_cache"]
    print(f"Prefix cache shared-prompt stream: hits={pc['hits']} "
          f"misses={pc['misses']} prefill tokens skipped="
          f"{pc['tokens_skipped']} shared pages={pc['shared_pages']} "
          f"COW forks={pc['cow_forks']} cached pages="
          f"{pc['cached_pages']} (TTFT mean {m['ttft_ms_mean']:.1f} ms)")


if __name__ == "__main__":
    main()
