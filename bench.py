#!/usr/bin/env python
"""Benchmark entry for the driver: prints ONE JSON line.

Measures BASELINE.md configs on the one real chip:
- config 1: ResNet-50 ImageNet-shape training (imgs/sec/chip), bf16 AMP,
  whole step compiled via paddle.jit.train_step.
- config 3 (north star): LLaMA-style causal LM training tokens/sec/chip +
  MFU via the functional sharded Trainer (largest config that fits one
  chip; MFU is chip-count-invariant so it is comparable to the A100 bar).
- By default also measures config 2 (BERT-base MLM step), config 4
  (ERNIE fused-transformer decode), config 6 (SD-UNet step), and a
  Pallas-kernel validation pack (compiled-on-chip numerics + microbench
  vs the XLA composition). BENCH_FAST=1 limits the run to
  probe+resnet+llama. BENCH_BUDGET bounds total wall clock (default
  5400s); partial results are persisted to BENCH_PARTIAL.json after
  every config.

vs_baseline for config 1 compares against the public A100 MLPerf-class
number (~2500 imgs/s/chip fp16); for config 3 the bar is 50-55% MFU
(BASELINE.md). Every timed window ends in ``block_until_ready``.

The platform is JAX's own choice (``JAX_PLATFORMS``). One process uses
the chip at a time: this parent never imports jax, and runs each config
to its end in ONE child (``--config NAME``, hard timeout) before the
next starts; the audit children are pinned to the CPU. The parent
prints its one JSON line and exits NON-ZERO if any config failed, timed
out or was skipped — no stored result stands in for a live one.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np

def _peak_bw():
    # the shared peak table (keyed by device_kind; PADDLE_TPU_PEAK_HBM_BW
    # overrides): the bench bw_frac and the roofline observatory's
    # achieved_bw_frac must divide by the SAME denominator
    from paddle_tpu.observability.compile import device_peak_hbm_bw
    return device_peak_hbm_bw()[0]


def _repro_meta():
    """Reproducibility stamp next to the timing rows: two banked bench
    runs are only comparable when the toolchain and the kernel-shaping
    knobs match — jax/jaxlib versions, the scoped-VMEM budget the fused
    dispatch predicates honor, and whether an autotune winners table
    was live (its block shapes move the timed kernels)."""
    import jax
    import jaxlib
    from paddle_tpu.ops.pallas._util import fused_vmem_budget
    meta = {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "fused_vmem_budget_env": os.environ.get(
            "PADDLE_TPU_FUSED_VMEM_BUDGET"),
        "fused_vmem_budget": fused_vmem_budget(),
    }
    try:
        from paddle_tpu.ops.pallas.autotune import _cache
        path = _cache._path
        if os.path.exists(path):
            with open(path) as f:
                meta["autotune_entries"] = len(json.load(f))
            meta["autotune_table"] = path
        else:
            meta["autotune_entries"] = 0
            meta["autotune_table"] = None
    except Exception:  # noqa: BLE001 — a corrupt table is "unknown"
        meta["autotune_entries"] = None
    return meta


def _roofline_report():
    """Trace-only roofline rows for EVERY registered kernel at the
    catalog shapes (jax.eval_shape under launch capture — no device
    needed): each ALL_KERNEL_NAMES entry gets modeled bytes, FLOPs,
    intensity and its memory/compute bound. The bench cases above time
    whatever the platform can run; this table is the complete model,
    so a kernel missing here IS the regression signal."""
    from paddle_tpu.analysis.kernel_catalog import (ALL_KERNEL_NAMES,
                                                    capture_case,
                                                    kernel_cases)
    from paddle_tpu.observability.roofline import (kernel_cost,
                                                   peak_snapshot)
    rows, memo = {}, {}
    for case in kernel_cases():
        specs, err = capture_case(case)
        if err is not None:
            continue
        for spec in specs:
            if spec.name not in rows:
                rows[spec.name] = kernel_cost(spec, memo=memo)
    return {"kernels": rows,
            "missing": sorted(set(ALL_KERNEL_NAMES) - set(rows)),
            **peak_snapshot()}


def _timed_host_synced(fn, steps):
    """ms/call of `fn` over a window of `steps` calls that ends in
    ``block_until_ready`` (measured on the v5e: it waits for the device
    as long as a host read-back does — CHANGES.md PR 21); the compile +
    warmup call is outside the window."""
    import jax

    jax.block_until_ready(fn())   # compile + warmup
    t0 = time.perf_counter()
    out = None
    for _ in range(steps):
        out = fn()
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / steps * 1e3, 2)  # ms


def _peak():
    # the shared peak table (keyed by device_kind; PADDLE_TPU_PEAK_FLOPS
    # overrides): the formula MFU and the cost-analysis MFU in one
    # capture must divide by the SAME denominator
    from paddle_tpu.observability.compile import device_peak_flops
    return device_peak_flops()[0]


# --------------------------------------------------------------------------
# individual configs (each runs in its own subprocess)
# --------------------------------------------------------------------------

def _audit_gate(run_audit, counters):
    """Shared pre-window static-audit hook (BENCH_AUDIT=0 opts out):
    runs the component's audit, returns its warning+error finding
    count from the adopted counter dict, and never kills the bench —
    a broken audit is a warning, a broken bench is a lost capture."""
    if os.environ.get("BENCH_AUDIT", "1") == "0":
        return None
    try:
        run_audit()
        return counters.get("audit_findings", 0)
    except Exception as e:  # noqa: BLE001
        import warnings
        warnings.warn(f"program audit failed: {e}")
        return None


def _kernel_audit(out):
    """Pre-``kernels`` static geometry audit (BENCH_KERNEL_AUDIT=0 opts
    out): run tools/kernel_audit.py as the real CLI against the
    committed KERNEL_AUDIT_BASELINE.json — a kernel whose launch
    geometry regressed (grid floor-drop, VMEM overcommit, dispatch-key
    gap) fails the audit BEFORE the bench spends a window timing it.
    Like the program audit, a failure marks the capture
    (``kernel_audit.rc``); it never kills the bench."""
    if os.environ.get("BENCH_KERNEL_AUDIT", "1") == "0":
        return
    import tempfile
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "kernel_audit.py")
    res_path = None
    try:
        with tempfile.NamedTemporaryFile("w", suffix=".json",
                                         delete=False) as f:
            res_path = f.name
        # pin the child to CPU: the audit only jax.eval_shape's, and a
        # TPU-backend init would contend with (or hang behind) the chip
        # the bench windows are about to use
        p = subprocess.run(
            [sys.executable, tool, "--json", res_path, "--quiet"],
            capture_output=True, text=True, timeout=600,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        audit = {"rc": p.returncode}
        try:
            with open(res_path) as f:
                audit["summary"] = json.load(f).get("summary", {})
        except (OSError, json.JSONDecodeError):
            pass
        if p.returncode != 0:
            audit["stderr"] = (p.stderr or "")[-400:]
            print(f"[bench] kernel audit failed (rc={p.returncode}): "
                  f"{(p.stderr or '').strip()[-200:]}", file=sys.stderr)
        out["kernel_audit"] = audit
    except Exception as e:  # noqa: BLE001 — audit is evidence, not bench
        out["kernel_audit"] = {"error": f"{type(e).__name__}: {e}"[:200]}
    finally:
        if res_path:
            try:
                os.unlink(res_path)
            except OSError:
                pass


def _lifecycle_audit(out):
    """Pre-serving lifecycle model-checker gate (BENCH_LIFECYCLE=0 opts
    out): run tools/lifecycle_audit.py as the real CLI against the
    committed LIFECYCLE_BASELINE.json — exhaustive small-scope
    exploration of the page/slot/COW/spill/handoff state machine. A
    scheduler-state-machine regression (page leak, refcount drift,
    deadlock) fails the audit BEFORE the bench spends windows timing
    the serving configs. Like the other audits, a failure marks the
    capture (``lifecycle_audit.rc``); it never kills the bench."""
    if os.environ.get("BENCH_LIFECYCLE", "1") == "0":
        return
    import tempfile
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "lifecycle_audit.py")
    res_path = None
    try:
        with tempfile.NamedTemporaryFile("w", suffix=".json",
                                         delete=False) as f:
            res_path = f.name
        # pin the child to CPU: the model checker is pure host-side
        # Python (BlockManager/PrefixCache/AdmissionQueue); a TPU
        # backend init would contend with the bench's chip for nothing
        p = subprocess.run(
            [sys.executable, tool, "--json", res_path, "--quiet"],
            capture_output=True, text=True, timeout=600,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        audit = {"rc": p.returncode}
        try:
            with open(res_path) as f:
                audit["summary"] = json.load(f).get("summary", {})
        except (OSError, json.JSONDecodeError):
            pass
        if p.returncode != 0:
            audit["stderr"] = (p.stderr or "")[-400:]
            print(f"[bench] lifecycle audit failed (rc={p.returncode}): "
                  f"{(p.stderr or '').strip()[-200:]}", file=sys.stderr)
        out["lifecycle_audit"] = audit
    except Exception as e:  # noqa: BLE001 — audit is evidence, not bench
        out["lifecycle_audit"] = {"error": f"{type(e).__name__}: {e}"[:200]}
    finally:
        if res_path:
            try:
                os.unlink(res_path)
            except OSError:
                pass


def _kernel_gate(out):
    """Post-window per-kernel regression gate (BENCH_KERNEL_GATE=0 opts
    out): diff the fresh ``kernels`` capture against the banked BENCH
    trajectory through tools/kernel_bench_gate.py — run as the real CLI
    so its nonzero-exit contract is exercised, but a regression only
    marks the capture (``kernel_gate.rc``); it never kills the bench,
    the driver grades the JSON."""
    if os.environ.get("BENCH_KERNEL_GATE", "1") == "0":
        return
    cap = out.get("kernels")
    if not isinstance(cap, dict) or "error" in cap:
        return
    import tempfile
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "kernel_bench_gate.py")
    cap_path = res_path = None
    try:
        with tempfile.NamedTemporaryFile("w", suffix=".json",
                                         delete=False) as f:
            json.dump({"kernels": cap}, f)
            cap_path = f.name
        res_path = cap_path + ".gate"
        p = subprocess.run(
            [sys.executable, tool, "--capture", cap_path,
             "--json", res_path, "--quiet"],
            capture_output=True, text=True, timeout=120)
        gate = {"rc": p.returncode}
        try:
            with open(res_path) as f:
                gate.update(json.load(f))
        except (OSError, json.JSONDecodeError):
            pass
        if p.returncode != 0:
            gate["stderr"] = (p.stderr or "")[-400:]
            print(f"[bench] kernel gate failed (rc={p.returncode}): "
                  f"{(p.stderr or '').strip()[-200:]}", file=sys.stderr)
        # roofline arm of the same gate (BENCH_ROOFLINE=0 opts out with
        # the report itself): achieved-bandwidth regressions, same
        # SKIP-on-no-reference semantics
        if os.environ.get("BENCH_ROOFLINE", "1").lower() \
                not in ("0", "false"):
            pr = subprocess.run(
                [sys.executable, tool, "--capture", cap_path,
                 "--roofline", "--json", res_path, "--quiet"],
                capture_output=True, text=True, timeout=120)
            roof = {"rc": pr.returncode}
            try:
                with open(res_path) as f:
                    roof.update(json.load(f))
            except (OSError, json.JSONDecodeError):
                pass
            if pr.returncode != 0:
                roof["stderr"] = (pr.stderr or "")[-400:]
                print(f"[bench] roofline gate failed "
                      f"(rc={pr.returncode}): "
                      f"{(pr.stderr or '').strip()[-200:]}",
                      file=sys.stderr)
            gate["roofline"] = roof
        out["kernel_gate"] = gate
    except Exception as e:  # noqa: BLE001 — gate is evidence, not bench
        out["kernel_gate"] = {"error": f"{type(e).__name__}: {e}"[:200]}
    finally:
        for pth in (cap_path, res_path):
            if pth:
                try:
                    os.unlink(pth)
                except OSError:
                    pass


def bench_resnet50(steps=20, batch=256, amp_level=None):
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import resnet50

    amp_level = amp_level or os.environ.get("BENCH_RESNET_AMP", "O1")
    paddle.seed(0)
    net = resnet50(num_classes=1000)
    net.train()
    opt = paddle.optimizer.Momentum(0.1, parameters=net.parameters())
    ts = paddle.jit.train_step(net, F.cross_entropy, opt,
                               amp_level=amp_level, amp_dtype="bfloat16")
    x = paddle.to_tensor(np.random.randn(batch, 3, 224, 224)
                         .astype(np.float32))
    y = paddle.to_tensor(np.random.randint(0, 1000, batch))

    loss = ts(x, y)
    float(loss)  # warmup + compile, host-synced
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = ts(x, y)
    final = float(loss)  # host transfer syncs the chain
    dt = time.perf_counter() - t0
    ips = steps * batch / dt
    return {"metric": "resnet50_train_imgs_per_sec_per_chip",
            "value": round(ips, 2), "unit": "imgs/sec/chip",
            "vs_baseline": round(ips / 2500.0, 4), "batch": batch,
            "amp": amp_level, "loss": round(final, 4)}


def bench_llama(steps=8, batch=2, seq=2048, hidden=2048, layers=12,
                inter=5504, accumulate=None, moment_dtype=None):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama import (LlamaConfig, init_params, loss_fn,
                                         param_shardings)
    from paddle_tpu.distributed.trainer import (MeshConfig, Trainer,
                                                make_mesh)

    cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                      intermediate_size=inter, num_hidden_layers=layers,
                      num_attention_heads=hidden // 128,
                      num_key_value_heads=hidden // 128,
                      max_position_embeddings=seq)
    # accumulate>1: micro-batch gradient accumulation (reference Fleet
    # accumulate_steps) — amortizes the per-param optimizer pass over
    # acc micro-batches of tokens
    acc = accumulate if accumulate is not None \
        else int(os.environ.get("BENCH_LLAMA_ACC", "1"))
    mesh = make_mesh(MeshConfig())
    params = init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(v.size for v in jax.tree_util.tree_leaves(params))
    mdt = {"bfloat16": jnp.bfloat16, "float32": None,
           None: None}[moment_dtype]
    # observability (default on, BENCH_TRAIN_OBS=0 to disable): per-step
    # phase histograms, compile telemetry + automatic MFU, host-vs-device
    # gap detection, and the per-step timeline banked as JSONL
    obs_on = os.environ.get("BENCH_TRAIN_OBS", "1") != "0"
    tr = Trainer(lambda p, t, l: loss_fn(p, t, l, cfg), mesh,
                 param_shardings(mesh, cfg), lr=1e-4,
                 accumulate_steps=acc, moment_dtype=mdt,
                 observability=obs_on)
    state = tr.init_state(params)
    shape = (acc, batch, seq) if acc > 1 else (batch, seq)
    toks = jnp.asarray(np.random.randint(0, 32000, shape), jnp.int32)
    labels = jnp.roll(toks, -1, axis=-1)

    state, m = tr.step(state, toks, labels)
    float(m["loss"])  # warmup + compile — ONE step again: the x64
    # master promotion that used to change the state signature after
    # step 1 (and force a second warmup step here) is fixed at the
    # source, with the fp32 bias correction in _adamw_update
    # static program audit before the timed window: the auditor's
    # dtype/donation/retrace/collective/constant passes gate the
    # steady-state program this window is about to measure
    audit_findings = _audit_gate(
        lambda: tr.audit(state, toks, labels), tr.counters)
    tr.reset_metrics()    # restart distributions + arm compile watchdog
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = tr.step(state, toks, labels)
    float(m["loss"])
    dt = time.perf_counter() - t0
    tps = steps * acc * batch * seq / dt
    # causal attention adds ~6*L*S*D flops/token on top of 6N
    flops_per_tok = 6 * n_params + 6 * cfg.num_hidden_layers * seq * \
        cfg.hidden_size
    mfu = tps * flops_per_tok / _peak()
    out = {"metric": "llama_train_tokens_per_sec_per_chip",
           "value": round(tps, 1), "unit": "tokens/sec/chip",
           "mfu": round(mfu, 4), "params": int(n_params), "batch": batch,
           "seq": seq, "accumulate": acc, "hidden": hidden,
           "layers": layers,
           **({"moment_dtype": moment_dtype} if moment_dtype else {}),
           **({"audit_findings": audit_findings}
              if audit_findings is not None else {}),
           "vs_baseline_mfu": round(mfu / 0.525, 4)}
    if obs_on:
        tm = tr.metrics()
        # flags the measurement mode in the capture: the observed loop
        # host-syncs every step (one block_until_ready + scalar d2h),
        # so its tokens/s is not directly comparable to a BENCH_TRAIN_OBS=0
        # run or to pre-r9 captures (which also timed a hidden recompile
        # — see the two-step warmup above)
        out["observed_loop"] = True
        out["step_ms"] = tm["latency"]["step_ms"]
        out["phase_ms_mean"] = {
            k: tm["latency"][k]["mean"]
            for k in ("stage_ms", "dispatch_ms", "sync_ms")}
        out["compiles_in_window"] = tm["retrace_warnings"]
        out["host_gap_findings"] = tm["host_gap_findings"]
        if tm["mfu"]:
            out["mfu_cost_analysis"] = tm["mfu"]["mfu"]
            out["flops_per_step_per_device_cost_analysis"] = \
                tm["mfu"]["flops_per_step_per_device"]
        if tm["hbm"]:
            out["hbm_breakdown"] = tm["hbm"]
        tl_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_TRAIN_TIMELINE.jsonl")
        try:
            tr.write_timeline(tl_path)
            out["timeline_jsonl"] = tl_path
        except OSError:
            pass

    # -- fused-vs-unfused training A/B (BENCH_TRAIN_AB=0 opts out): the
    # SAME step through a trainer on the dispatched fused training
    # path ("auto": linear+CE custom_vjp, SwiGLU, RMSNorm backward +
    # residual epilogue where the registry supports them — the route
    # production runs, not a force that could VMEM-OOM past the
    # budget) and one pinned to the exact pre-fusion composition
    # ("ref") — per-step timing from the observability
    # histograms, HBM peak from memory_analysis(), MFU from
    # cost_analysis(). The training-side decode_ab: the capture carries
    # both sides of the fusion claim (step_ms + the [T, V]-logit HBM
    # traffic the chunked kernel never materializes), not just the
    # fused number.
    if os.environ.get("BENCH_TRAIN_AB", "1") != "0":
        import dataclasses as _dc

        def _train_side(mode, ab_steps):
            cfg_s = _dc.replace(cfg, fused_train=mode)
            # observability rides the same BENCH_TRAIN_OBS opt-out as
            # the main window (and the multi-device observed trainer
            # has a known step-2 AOT sharding limitation, so the A/B
            # must stay runnable with it off) — the wall-clock mean is
            # always reported, the richer step_ms/HBM/MFU telemetry
            # only when observed
            tr_s = Trainer(lambda p, t, l: loss_fn(p, t, l, cfg_s), mesh,
                           param_shardings(mesh, cfg_s), lr=1e-4,
                           accumulate_steps=acc, moment_dtype=mdt,
                           observability=obs_on)
            st = tr_s.init_state(params)
            st, mm = tr_s.step(st, toks, labels)      # compile + warmup
            float(mm["loss"])
            tr_s.reset_metrics()
            t1 = time.perf_counter()
            for _ in range(ab_steps):
                st, mm = tr_s.step(st, toks, labels)
            float(mm["loss"])
            dt_s = time.perf_counter() - t1
            side = {"mode": mode,
                    "step_ms_mean": round(dt_s / ab_steps * 1e3, 3),
                    "tokens_per_sec": round(
                        ab_steps * acc * batch * seq / dt_s, 1)}
            if obs_on:
                tm_s = tr_s.metrics()
                side["step_ms"] = tm_s["latency"]["step_ms"]
                if tm_s.get("mfu"):
                    side["mfu_cost_analysis"] = tm_s["mfu"]["mfu"]
                if tm_s.get("hbm"):
                    side["hbm_peak_bytes"] = tm_s["hbm"].get(
                        "total_bytes")
                    side["hbm_temp_bytes"] = tm_s["hbm"].get(
                        "temp_bytes")
            return side

        try:
            ab_steps = int(os.environ.get("BENCH_TRAIN_AB_STEPS", steps))
            fused_side = _train_side("auto", ab_steps)
            unfused_side = _train_side("ref", ab_steps)
            ab = {"fused": fused_side, "unfused": unfused_side}
            f50 = (fused_side.get("step_ms") or {}).get("p50") \
                or fused_side["step_ms_mean"]
            u50 = (unfused_side.get("step_ms") or {}).get("p50") \
                or unfused_side["step_ms_mean"]
            if f50 and u50:
                ab["fused_train_speedup"] = round(u50 / f50, 3)
            fh, uh = (fused_side.get("hbm_peak_bytes"),
                      unfused_side.get("hbm_peak_bytes"))
            if fh and uh:
                ab["hbm_peak_saved_bytes"] = int(uh - fh)
            out["train_ab"] = ab
        except Exception as e:  # noqa: BLE001 — A/B is evidence, not
            out["train_ab"] = {                      # the bench
                "error": f"{type(e).__name__}: {e}"[:200]}
    return out


def bench_llama_breakdown(batch=4, seq=2048, hidden=1536, layers=8,
                          inter=4096):
    """LLaMA-step bottleneck decomposition (the llama analog of
    resnet_breakdown): times fwd-only, fwd+bwd and the full trainer step
    with the Pallas flash-attention path vs the XLA jnp composition
    (FLAGS_use_flash_attention=0), plus a no-remat fwd+bwd variant, so
    one child run pinpoints whether low MFU comes from the attention
    kernel, remat recompute, the optimizer or the step plumbing. Any
    silent Pallas->XLA fallback is captured in the JSON."""
    import warnings as _warnings

    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.flags import GLOBAL_FLAGS
    from paddle_tpu.models.llama import (LlamaConfig, init_params, loss_fn,
                                         param_shardings)
    from paddle_tpu.distributed.trainer import (MeshConfig, Trainer,
                                                make_mesh)

    batch = int(os.environ.get("BENCH_BD_BATCH", batch))
    seq = int(os.environ.get("BENCH_BD_SEQ", seq))
    hidden = int(os.environ.get("BENCH_BD_HIDDEN", hidden))
    layers = int(os.environ.get("BENCH_BD_LAYERS", layers))
    inter = int(os.environ.get("BENCH_BD_INTER", inter))

    def make_cfg(remat=True):
        return LlamaConfig(vocab_size=32000, hidden_size=hidden,
                           intermediate_size=inter,
                           num_hidden_layers=layers,
                           num_attention_heads=hidden // 128,
                           num_key_value_heads=hidden // 128,
                           max_position_embeddings=seq, remat=remat)

    cfg = make_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(v.size for v in jax.tree_util.tree_leaves(params))
    toks = jnp.asarray(np.random.randint(0, 32000, (batch, seq)), jnp.int32)
    labels = jnp.roll(toks, -1, axis=1)
    res = {"metric": "llama_step_breakdown", "batch": batch, "seq": seq,
           "hidden": hidden, "layers": layers, "params": int(n_params)}

    def timed(fn, steps=5):
        return _timed_host_synced(fn, steps)

    fwd = jax.jit(lambda p, t, l: loss_fn(p, t, l, cfg))
    grad = jax.jit(lambda p, t, l: jax.grad(
        lambda q: loss_fn(q, t, l, cfg))(p))
    cfg_nr = make_cfg(remat=False)
    grad_nr = jax.jit(lambda p, t, l: jax.grad(
        lambda q: loss_fn(q, t, l, cfg_nr))(p))

    # full trainer step FIRST: the xla_attn A/B leg below is expected to
    # OOM at big shapes, and a TPU OOM poisons the client for the rest
    # of the process — the headline number must already be banked.
    # The legs need their own copy: init_state's device_put aliases
    # same-sharding inputs, and the donated step deletes its state.
    params_legs = jax.tree_util.tree_map(
        lambda v: jnp.array(v, copy=True), params)
    flag_prev = GLOBAL_FLAGS.get("use_flash_attention")
    mesh = make_mesh(MeshConfig())
    tr = Trainer(lambda p, t, l: loss_fn(p, t, l, cfg), mesh,
                 param_shardings(mesh, cfg), lr=1e-4)
    state = tr.init_state(params)

    def step():
        nonlocal state
        state, m = tr.step(state, toks, labels)
        return m["loss"]

    res["full_step_ms"] = timed(step)
    tps = batch * seq / (res["full_step_ms"] / 1e3)
    flops_per_tok = 6 * n_params + 6 * layers * seq * hidden
    res["value"] = round(tps, 1)
    res["unit"] = "tokens/sec/chip"
    res["mfu"] = round(tps * flops_per_tok / _peak(), 4)

    legs = [(True, "flash"), (False, "xla_attn")]
    if not flag_prev:      # honor FLAGS_use_flash_attention=0: xla leg first
        legs.reverse()
    for flag, tag in legs:
        GLOBAL_FLAGS.set("use_flash_attention", flag)
        try:
            res[f"forward_ms_{tag}"] = timed(
                lambda: fwd(params_legs, toks, labels))
            res[f"fwd_bwd_ms_{tag}"] = timed(
                lambda: grad(params_legs, toks, labels))
            if flag:
                res["fwd_bwd_ms_noremat"] = timed(
                    lambda: grad_nr(params_legs, toks, labels))
        except Exception as e:  # noqa: BLE001 — e.g. xla_attn path OOM
            res[f"error_{tag}"] = f"{type(e).__name__}: {e}"[:160]
        fwd.clear_cache()
        grad.clear_cache()
    GLOBAL_FLAGS.set("use_flash_attention", flag_prev)
    head_tag = legs[0][1]  # the leg the full step above actually ran with
    if f"fwd_bwd_ms_{head_tag}" in res:
        res["optimizer_residual_ms"] = round(
            res["full_step_ms"] - res[f"fwd_bwd_ms_{head_tag}"], 2)
    try:
        trace_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "profile_llama")
        with jax.profiler.trace(trace_dir):
            loss = step()
            np.asarray(jnp.ravel(loss)[0])
        res["xplane_trace"] = trace_dir
    except Exception as e:  # noqa: BLE001 — trace is best-effort
        res["xplane_error"] = f"{type(e).__name__}: {e}"[:120]
    return res


def bench_bert(steps=10, batch=32, seq=128):
    """BASELINE config 2: BERT-base MLM training step (single chip; the
    DP axis adds only an allreduce that rides ICI on real pods)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.bert import (BertConfig, init_params, mlm_loss,
                                        param_shardings)
    from paddle_tpu.distributed.trainer import (MeshConfig, Trainer,
                                                make_mesh)

    cfg = BertConfig()  # base: 12L/768H/12A
    mesh = make_mesh(MeshConfig())
    params = init_params(cfg, jax.random.PRNGKey(0))
    tr = Trainer(lambda p, t, l: mlm_loss(p, t, l, cfg), mesh,
                 param_shardings(mesh, cfg), lr=1e-4)
    state = tr.init_state(params)
    toks = jnp.asarray(np.random.randint(0, cfg.vocab_size, (batch, seq)),
                       jnp.int32)
    labels = jnp.asarray(np.random.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    state, m = tr.step(state, toks, labels)
    float(m["loss"])  # warmup + compile
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = tr.step(state, toks, labels)
    float(m["loss"])
    dt = time.perf_counter() - t0
    sps = steps * batch / dt
    return {"metric": "bert_base_mlm_seqs_per_sec_per_chip",
            "value": round(sps, 2), "unit": "seqs/sec/chip",
            "batch": batch, "seq": seq}


def bench_ernie_infer(batch=8, ctx=512, gen=64):
    """BASELINE config 4: fused-transformer decode — the compiled
    generate loop (prefill + lax.scan of cached decode steps) on an
    ERNIE-class 12L/1024H decoder."""
    import jax
    from paddle_tpu.inference.generation import GenerationConfig, generate
    from paddle_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                      intermediate_size=4096, num_hidden_layers=12,
                      num_attention_heads=16, num_key_value_heads=16,
                      max_position_embeddings=ctx + gen)
    import jax.numpy as jnp
    params = init_params(cfg, jax.random.PRNGKey(0))
    # pre-stage the prompt on device: a blocking h2d must not be billed
    # to every generate call
    toks = jnp.asarray(np.random.randint(0, 32000, (batch, ctx)), jnp.int32)
    g = GenerationConfig(max_new_tokens=gen, greedy=True)
    steps = 4
    ms = _timed_host_synced(lambda: generate(params, toks, cfg, g),
                            steps=steps)
    return {"metric": "ernie_decode_tokens_per_sec_per_chip",
            "value": round(batch * gen / (ms / 1e3), 1),
            "unit": "tokens/sec/chip",
            "batch": batch, "ctx": ctx, "gen": gen}


def bench_paged_decode():
    """VERDICT r4 Next #5: time generate_paged on chip at serving shapes,
    Pallas paged-attention kernel vs the XLA gather composition (the
    registry op's ``xla`` variant, pinned). Reference capability: the paged-KV fused
    decode in paddle/phi/kernels/fusion/ (block_multihead_attention).
    Each (batch, ctx) point reports tokens/s for both paths."""
    import jax
    import jax.numpy as jnp
    import contextlib
    import paddle_tpu.ops.paged_attention  # noqa: F401 — registers the op
    from paddle_tpu.inference.generation import (GenerationConfig,
                                                 generate_paged)
    from paddle_tpu.ops.pallas.registry import KERNELS
    from paddle_tpu.models.llama import LlamaConfig, init_params

    gen_n = int(os.environ.get("BENCH_PAGED_GEN", "64"))
    points = [(8, 512), (32, 512), (8, 2048), (32, 2048)]
    if os.environ.get("BENCH_PAGED_POINTS"):
        points = [tuple(map(int, p.split("x")))
                  for p in os.environ["BENCH_PAGED_POINTS"].split(",")]
    res = {"metric": "paged_decode_tokens_per_sec_per_chip", "value": 0.0,
           "unit": "tokens/sec/chip", "gen": gen_n, "points": {}}
    best = 0.0
    for batch, ctx in points:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=4096, num_hidden_layers=12,
                          num_attention_heads=16, num_key_value_heads=16,
                          max_position_embeddings=ctx + gen_n)
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jnp.asarray(np.random.randint(0, 32000, (batch, ctx)),
                           jnp.int32)
        g = GenerationConfig(max_new_tokens=gen_n, greedy=True)
        point = {}
        xla = KERNELS.force("paged_attention_decode", "xla")
        for label, pin, cdt in (("pallas", contextlib.nullcontext(), None),
                                ("xla_gather", xla, None),
                                ("int8_cache", xla, "int8")):
            try:
                with pin:
                    ms = _timed_host_synced(
                        lambda: generate_paged(params, toks, cfg, g,
                                               cache_dtype=cdt),
                        steps=3)
                point[label] = round(batch * gen_n / (ms / 1e3), 1)
            except Exception as e:  # noqa: BLE001
                point[label] = f"{type(e).__name__}: {e}"[:160]
        if isinstance(point.get("pallas"), float) and \
                isinstance(point.get("xla_gather"), float):
            point["speedup"] = round(point["pallas"]
                                     / max(point["xla_gather"], 1e-9), 3)
        res["points"][f"{batch}x{ctx}"] = point
        if isinstance(point.get("pallas"), float):
            best = max(best, point["pallas"])
        del params
    res["value"] = best
    return res


def bench_serving_engine():
    """Mixed-arrival serving: the continuous-batching ServingEngine vs
    static `generate_paged` batches on the SAME Poisson arrival trace.
    The static baseline forms FIFO batches of `capacity`, each batch
    waits for its last arrival and drains at the pace of its slowest
    request; the engine admits each request the step after it arrives
    and recycles finished slots immediately. Reports tokens/s, TTFT /
    TPOT / queue-wait p50/p95/p99 (observability layer), decode-slot
    utilization, and banks the full per-phase timeline as JSONL next
    to the BENCH capture (tools/trace_summary.py reads it)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference.generation import (GenerationConfig,
                                                 generate_paged)
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models.llama import LlamaConfig, init_params

    cap = int(os.environ.get("BENCH_SERVE_CAPACITY", "8"))
    R = int(os.environ.get("BENCH_SERVE_REQUESTS", str(3 * cap)))
    R = (R // cap) * cap or cap   # full static batches, no retrace
    ctx = int(os.environ.get("BENCH_SERVE_CTX", "256"))
    gen_n = int(os.environ.get("BENCH_SERVE_GEN", "64"))
    rate = float(os.environ.get("BENCH_SERVE_RATE_HZ", "4.0"))
    hidden = int(os.environ.get("BENCH_SERVE_HIDDEN", "1024"))
    layers = int(os.environ.get("BENCH_SERVE_LAYERS", "12"))
    cdt = os.environ.get("BENCH_SERVE_CACHE_DTYPE") or None

    cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                      intermediate_size=hidden * 4,
                      num_hidden_layers=layers,
                      num_attention_heads=hidden // 64,
                      num_key_value_heads=hidden // 64,
                      max_position_embeddings=ctx + gen_n)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, 32000, (R, ctx)).astype(np.int32)
    gaps = rng.exponential(1.0 / rate, R)
    gaps[0] = 0.0
    arrivals = np.cumsum(gaps)
    g = GenerationConfig(max_new_tokens=gen_n, greedy=True)

    # -- continuous batching (compile warmup outside the timed window) --
    # BENCH_TELEMETRY=0 opts out of the continuous telemetry plane
    # (series sampling + burn-rate/anomaly alerting over the run)
    tel = os.environ.get("BENCH_TELEMETRY", "1") != "0"
    eng = ServingEngine(params, cfg, capacity=cap, block_size=16,
                        max_seq_len=ctx + gen_n, cache_dtype=cdt,
                        prefill_buckets=(ctx,), observability=True,
                        telemetry=tel)
    eng.submit(prompts[0], GenerationConfig(max_new_tokens=2,
                                            greedy=True))
    eng.drain()
    # static program audit before the timed window (trace-only; the
    # trace counters it touches are snapshotted/restored inside)
    audit_findings = _audit_gate(eng.audit, eng.counters)
    eng.reset_metrics()   # also arms the retrace watchdog
    t0 = time.perf_counter()
    i = 0
    while i < R or not eng.idle:
        now = time.perf_counter() - t0
        while i < R and arrivals[i] <= now:
            eng.submit(prompts[i], g)
            i += 1
        if not eng.step() and i < R:
            time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
    eng_wall = time.perf_counter() - t0
    m = eng.metrics()
    eng_tps = R * gen_n / eng_wall

    # -- static baseline: measure one full batch, replay the timeline --
    batch = jnp.asarray(prompts[:cap])
    np.asarray(generate_paged(params, batch, cfg, g, cache_dtype=cdt))
    t1 = time.perf_counter()
    np.asarray(generate_paged(params, batch, cfg, g, cache_dtype=cdt))
    batch_s = time.perf_counter() - t1
    free_at, lat = 0.0, []
    for b0 in range(0, R, cap):
        formed = arrivals[b0 + cap - 1]      # FIFO batch waits for last
        end = max(formed, free_at) + batch_s
        free_at = end
        lat.extend(end - arrivals[j] for j in range(b0, b0 + cap))
    static_tps = R * gen_n / free_at

    # bank the per-phase timeline BEFORE the A/B burst below pushes
    # synthetic requests through the engine — the banked JSONL must
    # describe the same window as the reported distributions
    tl_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_SERVING_TIMELINE.jsonl")
    try:
        eng.write_timeline(tl_path)
    except OSError:
        tl_path = None
    # bank the telemetry series/alert log next to the timeline
    # (tools/telemetry_summary.py reads it)
    tel_path = None
    tel_alerts = None
    if tel and eng.telemetry is not None:
        tel_alerts = m["telemetry"]["alerts"]
        tel_path = eng.telemetry.write_jsonl(os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_SERVING_TELEMETRY.jsonl"))

    # -- kernels-vs-compositions decode A/B (BENCH_SERVE_AB=0 opts
    # out): the same full-capacity burst through the (already warm)
    # engine and a fresh engine whose two decode launches are pinned to
    # their XLA compositions, per-step decode timing read from the
    # observability histograms — the capture carries both sides
    ab = None
    if os.environ.get("BENCH_SERVE_AB", "1") != "0":
        def _burst_decode_ms(e):
            e.reset_metrics()
            for j in range(cap):
                e.submit(prompts[j], g)
            e.drain()
            return e.metrics()["latency"]["decode_step_ms"]

        try:
            from paddle_tpu.ops.pallas.registry import KERNELS
            fused_ms = _burst_decode_ms(eng)
            eng_u = ServingEngine(params, cfg, capacity=cap,
                                  block_size=16,
                                  max_seq_len=ctx + gen_n,
                                  cache_dtype=cdt,
                                  prefill_buckets=(ctx,),
                                  observability=True)
            with KERNELS.force("paged_attention_decode", "xla"), \
                    KERNELS.force("decode_mlp_block", "unfused"):
                eng_u.submit(prompts[0],
                             GenerationConfig(max_new_tokens=2,
                                              greedy=True))
                eng_u.drain()        # compile outside the measured burst
                unfused_ms = _burst_decode_ms(eng_u)
            f50, u50 = fused_ms.get("p50"), unfused_ms.get("p50")
            ab = {"variant": eng.decode_variant,
                  "fused_decode_step_ms": fused_ms,
                  "unfused_decode_step_ms": unfused_ms,
                  **({"fused_decode_speedup": round(u50 / f50, 3)}
                     if f50 and u50 else {})}
        except Exception as e:  # noqa: BLE001 — A/B is evidence, not
            ab = {"error": f"{type(e).__name__}: {e}"[:200]}  # the bench

    # full distributions (snapshotted into ``m`` before the A/B): a
    # short healthy window yields p50/p95/p99, not a single mean
    lat_m = m["latency"]
    return {"metric": "serving_engine_tokens_per_sec_per_chip",
            "value": round(eng_tps, 1), "unit": "tokens/sec/chip",
            "static_tokens_per_sec": round(static_tps, 1),
            "speedup_vs_static": round(eng_tps / max(static_tps, 1e-9),
                                       3),
            "ttft_ms_mean": m["ttft_ms_mean"],
            "ttft_ms": lat_m["ttft_ms"],
            "tpot_ms": lat_m["tpot_ms"],
            "queue_wait_ms": lat_m["queue_wait_ms"],
            "decode_step_ms": lat_m["decode_step_ms"],
            "static_latency_ms_mean": round(
                float(np.mean(lat)) * 1e3, 1),
            "slot_utilization": m["slot_utilization"],
            "decode_traces": m["decode_traces"],
            "prefill_traces": m["prefill_traces"],
            "retrace_warnings": m["retrace_warnings"],
            "prefill_tokens_per_sec": m["prefill_tokens_per_sec"],
            **({"audit_findings": audit_findings}
               if audit_findings is not None else {}),
            **({"decode_ab": ab} if ab is not None else {}),
            **({"timeline_jsonl": tl_path} if tl_path else {}),
            **({"telemetry_alerts": tel_alerts}
               if tel_alerts is not None else {}),
            **({"telemetry_jsonl": tel_path} if tel_path else {}),
            "requests": R, "capacity": cap, "ctx": ctx, "gen": gen_n,
            "arrival_rate_hz": rate,
            **({"cache_dtype": cdt} if cdt else {})}


def bench_serving_prefix_cache():
    """Shared-system-prompt serving (the dominant real traffic shape):
    every request is a long shared prefix + a short unique tail. Runs
    the SAME Poisson arrival trace through the ServingEngine twice —
    cold (no prefix cache) and warm (radix prefix cache on) — and
    reports TTFT, tokens/s and prefill-tokens-skipped. The warm engine
    prefills the shared prefix once; every later request admits with
    only its tail un-cached."""
    import jax
    from paddle_tpu.inference.generation import GenerationConfig
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models.llama import LlamaConfig, init_params

    cap = int(os.environ.get("BENCH_PREFIX_CAPACITY", "8"))
    R = int(os.environ.get("BENCH_PREFIX_REQUESTS", str(3 * cap)))
    shared = int(os.environ.get("BENCH_PREFIX_SHARED", "224"))
    tail = int(os.environ.get("BENCH_PREFIX_TAIL", "32"))
    gen_n = int(os.environ.get("BENCH_PREFIX_GEN", "32"))
    rate = float(os.environ.get("BENCH_PREFIX_RATE_HZ", "4.0"))
    hidden = int(os.environ.get("BENCH_PREFIX_HIDDEN", "1024"))
    layers = int(os.environ.get("BENCH_PREFIX_LAYERS", "12"))
    ctx = shared + tail

    cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                      intermediate_size=hidden * 4,
                      num_hidden_layers=layers,
                      num_attention_heads=hidden // 64,
                      num_key_value_heads=hidden // 64,
                      max_position_embeddings=ctx + gen_n)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    sys_prompt = rng.randint(0, 32000, (shared,))
    prompts = [np.concatenate([sys_prompt,
                               rng.randint(0, 32000, (tail,))])
               .astype(np.int32) for _ in range(R)]
    gaps = rng.exponential(1.0 / rate, R)
    gaps[0] = 0.0
    arrivals = np.cumsum(gaps)
    g = GenerationConfig(max_new_tokens=gen_n, greedy=True)
    # second warmup prompt: shares the system prefix with a fresh tail,
    # so the warm engine compiles its suffix-bucket prefill program
    # outside the timed window (the cold engine re-runs the full bucket)
    warm2 = np.concatenate([sys_prompt, rng.randint(0, 32000, (tail,))
                            ]).astype(np.int32)

    def run_one(prefix_cache):
        # a pool big enough to keep the whole shared prefix resident
        blocks = (cap + 2) * (-(-(ctx + gen_n) // 16)) + 1
        eng = ServingEngine(params, cfg, capacity=cap, block_size=16,
                            max_seq_len=ctx + gen_n, num_blocks=blocks,
                            prefill_buckets=(tail, ctx),
                            prefix_cache=prefix_cache,
                            observability=True)
        gw = GenerationConfig(max_new_tokens=2, greedy=True)
        eng.submit(prompts[0][:ctx], gw)
        eng.drain()                      # compile warmup + prefix seed
        eng.submit(warm2, gw)            # warm the suffix bucket too
        eng.drain()
        eng.reset_metrics()
        t0 = time.perf_counter()
        i = 0
        while i < R or not eng.idle:
            now = time.perf_counter() - t0
            while i < R and arrivals[i] <= now:
                eng.submit(prompts[i], g)
                i += 1
            if not eng.step() and i < R:
                time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
        wall = time.perf_counter() - t0
        tl = None
        if prefix_cache:
            try:
                tl = eng.write_timeline(os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "BENCH_PREFIX_TIMELINE.jsonl"))
            except OSError:
                pass
        return eng.metrics(), wall, tl

    warm_m, warm_wall, warm_tl = run_one(True)
    cold_m, cold_wall, _ = run_one(False)
    pc = warm_m.get("prefix_cache", {})
    return {"metric": "serving_prefix_cache_ttft_ms_mean",
            "value": warm_m["ttft_ms_mean"], "unit": "ms",
            "warm_ttft_ms": warm_m["latency"]["ttft_ms"],
            "cold_ttft_ms": cold_m["latency"]["ttft_ms"],
            "warm_queue_wait_ms": warm_m["latency"]["queue_wait_ms"],
            "retrace_warnings": warm_m["retrace_warnings"],
            "cold_ttft_ms_mean": cold_m["ttft_ms_mean"],
            "ttft_speedup": round(
                (cold_m["ttft_ms_mean"] or 0.0)
                / max(warm_m["ttft_ms_mean"] or 1e-9, 1e-9), 3),
            "warm_tokens_per_sec": round(R * gen_n / warm_wall, 1),
            "cold_tokens_per_sec": round(R * gen_n / cold_wall, 1),
            "prefill_tokens_skipped": pc.get("tokens_skipped", 0),
            "prefix_hits": pc.get("hits", 0),
            "cow_forks": pc.get("cow_forks", 0),
            "evicted_pages": pc.get("evicted_pages", 0),
            "warm_prefill_chunks": warm_m["prefill_chunks"],
            "cold_prefill_chunks": cold_m["prefill_chunks"],
            "warm_prefill_tokens_per_sec":
                warm_m["prefill_tokens_per_sec"],
            "cold_prefill_tokens_per_sec":
                cold_m["prefill_tokens_per_sec"],
            **({"timeline_jsonl": warm_tl} if warm_tl else {}),
            "requests": R, "capacity": cap, "shared_prefix": shared,
            "tail": tail, "gen": gen_n, "arrival_rate_hz": rate}


def bench_serving_prefill():
    """Prefill-heavy Poisson mix: fused vs unfused chunked prefill A/B
    (the r17 prefill-side megakernel). Mixed-length prompts (ragged
    chunks — the pad-FLOPs story) with short generations run through
    the SAME arrival trace twice: fused_prefill=False (the verbatim
    gather/cached_forward/scatter chunk) and the default fused route.
    Reports TTFT / prefill-chunk-time distributions, prefill tokens/s,
    the pad-token counter (the compute the ragged kernels skip where
    dispatched), the dispatched variant, and greedy parity between the
    two engines. Off-TPU dispatch falls back on both sides, so the
    capture proves structure + bit-parity; on TPU it carries the
    fused-vs-unfused TTFT claim. Banked next to serving_engine's
    decode_ab."""
    import jax
    from paddle_tpu.inference.generation import GenerationConfig
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models.llama import LlamaConfig, init_params

    cap = int(os.environ.get("BENCH_SPREFILL_CAPACITY", "8"))
    R = int(os.environ.get("BENCH_SPREFILL_REQUESTS", str(3 * cap)))
    ctx = int(os.environ.get("BENCH_SPREFILL_CTX", "256"))
    gen_n = int(os.environ.get("BENCH_SPREFILL_GEN", "8"))
    rate = float(os.environ.get("BENCH_SPREFILL_RATE_HZ", "6.0"))
    hidden = int(os.environ.get("BENCH_SPREFILL_HIDDEN", "1024"))
    layers = int(os.environ.get("BENCH_SPREFILL_LAYERS", "12"))

    cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                      intermediate_size=hidden * 4,
                      num_hidden_layers=layers,
                      num_attention_heads=hidden // 64,
                      num_key_value_heads=hidden // 64,
                      max_position_embeddings=ctx + gen_n)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    # MIXED lengths: uniform in [ctx//4, ctx] so chunks are ragged
    lens = rng.randint(max(ctx // 4, 8), ctx + 1, R)
    prompts = [rng.randint(0, 32000, (int(s),)).astype(np.int32)
               for s in lens]
    gaps = rng.exponential(1.0 / rate, R)
    gaps[0] = 0.0
    arrivals = np.cumsum(gaps)
    g = GenerationConfig(max_new_tokens=gen_n, greedy=True)
    buckets = tuple(sorted({min(64, ctx), ctx}))

    def run(fp):
        eng = ServingEngine(params, cfg, capacity=cap, block_size=16,
                            max_seq_len=ctx + gen_n,
                            prefill_buckets=buckets, fused_prefill=fp,
                            observability=True)
        gw = GenerationConfig(max_new_tokens=2, greedy=True)
        for s in buckets:           # warm every bucket + decode
            eng.submit(rng.randint(0, 32000, (s - 2,))
                       .astype(np.int32), gw)
            eng.drain()
        eng.reset_metrics()
        reqs, t0, i = [], time.perf_counter(), 0
        while i < R or not eng.idle:
            now = time.perf_counter() - t0
            while i < R and arrivals[i] <= now:
                reqs.append(eng.submit(prompts[i], g))
                i += 1
            if not eng.step() and i < R:
                time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
        wall = time.perf_counter() - t0
        m = eng.metrics()
        side = {"ttft_ms": m["latency"]["ttft_ms"],
                "ttft_ms_mean": m["ttft_ms_mean"],
                "prefill_chunk_ms": m["latency"]["prefill_chunk_ms"],
                "prefill_tokens_per_sec": m["prefill_tokens_per_sec"],
                "tokens_per_sec": round(R * gen_n / wall, 1),
                "prefill_chunks": m["prefill_chunks"],
                "prefill_pad_tokens": m["prefill_pad_tokens"],
                "prefill_traces": m["prefill_traces"],
                "retrace_warnings": m["retrace_warnings"],
                "variant": m["prefill_variant"]}
        return side, [r.output_ids for r in reqs]

    unfused, out_u = run(False)
    fused, out_f = run(None)            # the default flag route
    matches = [bool(np.array_equal(a, b))
               for a, b in zip(out_f, out_u)]
    f_t, u_t = fused["ttft_ms_mean"], unfused["ttft_ms_mean"]
    return {"metric": "serving_prefill_fused_ttft_ms_mean",
            "value": f_t, "unit": "ms",
            "unfused_ttft_ms_mean": u_t,
            "ttft_speedup": (round(u_t / f_t, 3)
                             if f_t and u_t else None),
            "greedy_parity": round(sum(matches) / max(len(matches), 1),
                                   4),
            "fused": fused, "unfused": unfused,
            "pad_tokens_skipped_by_fused_dispatch":
                fused["prefill_pad_tokens"]
                if fused["variant"].get("attn") == "pallas_fused"
                else 0,
            "requests": R, "capacity": cap, "ctx": ctx, "gen": gen_n,
            "buckets": list(buckets), "arrival_rate_hz": rate}


def bench_serving_quant():
    """Weight-quantized serving A/B (r18): fp vs int8 vs int4 weights
    through the SAME Poisson arrival trace (the standard serving mix),
    one ServingEngine per mode over a shared model. Reports per mode:
    tokens/s, TTFT/TPOT distributions, the weight-HBM bytes each
    decode step streams (the bandwidth multiplier the quantization
    buys — int4 is ~4x less than bf16), the dispatched
    weight_quant_variant, plus the accuracy budget vs the fp engine:
    greedy flip fraction (per-token mismatches over the stream) and
    the max/mean absolute logit error of ONE dense forward on a fixed
    prompt. Off-TPU dispatch falls back to the dequantize-then-matmul
    composition on every side, so the capture proves structure +
    accuracy; on TPU it carries the fused dequant-matmul bandwidth
    claim."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference.generation import (GenerationConfig,
                                                 cached_forward,
                                                 init_cache)
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models.llama import LlamaConfig, init_params
    from paddle_tpu.quantization import ptq

    cap = int(os.environ.get("BENCH_SQUANT_CAPACITY", "4"))
    R = int(os.environ.get("BENCH_SQUANT_REQUESTS", str(3 * cap)))
    ctx = int(os.environ.get("BENCH_SQUANT_CTX", "128"))
    gen_n = int(os.environ.get("BENCH_SQUANT_GEN", "32"))
    rate = float(os.environ.get("BENCH_SQUANT_RATE_HZ", "4.0"))
    hidden = int(os.environ.get("BENCH_SQUANT_HIDDEN", "512"))
    layers = int(os.environ.get("BENCH_SQUANT_LAYERS", "6"))

    cfg = LlamaConfig(vocab_size=32000, hidden_size=hidden,
                      intermediate_size=hidden * 4,
                      num_hidden_layers=layers,
                      num_attention_heads=hidden // 64,
                      num_key_value_heads=hidden // 64,
                      max_position_embeddings=ctx + gen_n)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, 32000, (R, ctx)).astype(np.int32)
    gaps = rng.exponential(1.0 / rate, R)
    gaps[0] = 0.0
    arrivals = np.cumsum(gaps)
    g = GenerationConfig(max_new_tokens=gen_n, greedy=True)
    # quantize ONCE per mode (deterministic) so the engines and the
    # logit-error forward see the same trees
    trees = {"fp": params,
             "int8": ptq.quantize_weights(params, bits=8),
             "int4": ptq.quantize_weights(params, bits=4)}

    # accuracy budget: one dense forward at the bench shape per tree
    probe = jnp.asarray(prompts[:1])
    kc, vc = init_cache(cfg, 1, ctx)
    ref_logits = np.asarray(cached_forward(params, probe, cfg, kc, vc,
                                           0)[0][0, -1], np.float32)

    def run(mode):
        eng = ServingEngine(trees[mode], cfg, capacity=cap,
                            block_size=16, max_seq_len=ctx + gen_n,
                            prefill_buckets=(ctx,), observability=True)
        eng.submit(prompts[0], GenerationConfig(max_new_tokens=2,
                                                greedy=True))
        eng.drain()                      # compile outside the window
        eng.reset_metrics()
        reqs, t0, i = [], time.perf_counter(), 0
        while i < R or not eng.idle:
            now = time.perf_counter() - t0
            while i < R and arrivals[i] <= now:
                reqs.append(eng.submit(prompts[i], g))
                i += 1
            if not eng.step() and i < R:
                time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
        wall = time.perf_counter() - t0
        m = eng.metrics()
        side = {"tokens_per_sec": round(R * gen_n / wall, 1),
                "ttft_ms": m["latency"]["ttft_ms"],
                "tpot_ms": m["latency"]["tpot_ms"],
                "decode_step_ms": m["latency"]["decode_step_ms"],
                "weight_hbm_bytes": ptq.weight_hbm_bytes(trees[mode]),
                "weight_quant_variant": m["weight_quant_variant"],
                "decode_traces": m["decode_traces"],
                "retrace_warnings": m["retrace_warnings"]}
        if mode != "fp":
            kc, vc = init_cache(cfg, 1, ctx)
            lg = np.asarray(cached_forward(trees[mode], probe, cfg, kc,
                                           vc, 0)[0][0, -1], np.float32)
            side["max_logit_err_vs_fp"] = round(
                float(np.abs(lg - ref_logits).max()), 5)
            side["mean_logit_err_vs_fp"] = round(
                float(np.abs(lg - ref_logits).mean()), 6)
        return side, [r.tokens for r in reqs]

    sides, streams = {}, {}
    for mode in ("fp", "int8", "int4"):
        sides[mode], streams[mode] = run(mode)
    total = sum(len(t) for t in streams["fp"]) or 1
    for mode in ("int8", "int4"):
        flips = sum(a != b for tf, tq in zip(streams["fp"],
                                             streams[mode])
                    for a, b in zip(tf, tq))
        sides[mode]["greedy_flip_fraction"] = round(flips / total, 4)
        sides[mode]["requests_bit_identical"] = sum(
            tf == tq for tf, tq in zip(streams["fp"], streams[mode]))
    fp_b = sides["fp"]["weight_hbm_bytes"]
    return {"metric": "serving_quant_int4_weight_hbm_reduction",
            "value": round(fp_b / max(sides["int4"]["weight_hbm_bytes"],
                                      1), 3),
            "unit": "x fewer weight bytes/step",
            "int8_weight_hbm_reduction": round(
                fp_b / max(sides["int8"]["weight_hbm_bytes"], 1), 3),
            "fp": sides["fp"], "int8": sides["int8"],
            "int4": sides["int4"],
            "requests": R, "capacity": cap, "ctx": ctx, "gen": gen_n,
            "arrival_rate_hz": rate}


def bench_serving_tp():
    """Tensor-parallel serving A/B over the default backend's devices
    (it raises where there are fewer than tp; the row names the
    platform it ran on): the SAME Poisson arrival trace through a tp=1
    engine and a tp=N mesh-sharded engine (inference/tp.py). On a
    virtual CPU mesh (JAX_PLATFORMS=cpu) the capture proves STRUCTURE,
    not chip perf — greedy parity,
    program counts (1 decode program, <=1 trace/bucket under sharding),
    the declared collective schedule (flight-recorder calls/bytes) and
    the full TTFT/TPOT distributions for both sides, banked next to
    serving_engine's decode_ab the same way."""
    from paddle_tpu.distributed.dryrun import resolve_devices

    tp = int(os.environ.get("BENCH_TP_DEGREE", "4"))
    coll = os.environ.get("BENCH_TP_COLLECTIVE", "psum")
    devices = resolve_devices(max(tp, 2))

    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference import (GenerationConfig, ServingEngine,
                                      ServingMesh)
    from paddle_tpu.models.llama import LlamaConfig, init_params

    cap = int(os.environ.get("BENCH_TP_CAPACITY", "4"))
    R = int(os.environ.get("BENCH_TP_REQUESTS", str(3 * cap)))
    ctx = int(os.environ.get("BENCH_TP_CTX", "32"))
    gen_n = int(os.environ.get("BENCH_TP_GEN", "16"))
    rate = float(os.environ.get("BENCH_TP_RATE_HZ", "8.0"))
    hidden = int(os.environ.get("BENCH_TP_HIDDEN", "128"))
    layers = int(os.environ.get("BENCH_TP_LAYERS", "4"))
    cfg = LlamaConfig(vocab_size=8192, hidden_size=hidden,
                      intermediate_size=hidden * 4,
                      num_hidden_layers=layers,
                      num_attention_heads=hidden // 32,
                      num_key_value_heads=hidden // 32,
                      max_position_embeddings=ctx + gen_n,
                      dtype=jnp.float32, remat=False)
    with jax.default_device(devices[0]):
        params = init_params(cfg, jax.random.PRNGKey(0),
                             dtype=jnp.float32)
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, 8192, (R, ctx)).astype(np.int32)
    gaps = rng.exponential(1.0 / rate, R)
    gaps[0] = 0.0
    arrivals = np.cumsum(gaps)
    g = GenerationConfig(max_new_tokens=gen_n, greedy=True)

    def run(mesh):
        eng = ServingEngine(params, cfg, capacity=cap, block_size=16,
                            max_seq_len=ctx + gen_n,
                            prefill_buckets=(ctx,), mesh=mesh,
                            observability=True)
        eng.submit(prompts[0], GenerationConfig(max_new_tokens=2,
                                                greedy=True))
        eng.drain()                      # compile outside the window
        eng.reset_metrics()
        outs, t0, i = [], time.perf_counter(), 0
        reqs = []
        while i < R or not eng.idle:
            now = time.perf_counter() - t0
            while i < R and arrivals[i] <= now:
                reqs.append(eng.submit(prompts[i], g))
                i += 1
            if not eng.step() and i < R:
                time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
        wall = time.perf_counter() - t0
        m = eng.metrics()
        outs = [r.output_ids for r in reqs]
        side = {"tokens_per_sec": round(R * gen_n / wall, 1),
                "ttft_ms": m["latency"]["ttft_ms"],
                "tpot_ms": m["latency"]["tpot_ms"],
                "decode_step_ms": m["latency"]["decode_step_ms"],
                "decode_traces": m["decode_traces"],
                "prefill_traces": m["prefill_traces"],
                "retrace_warnings": m["retrace_warnings"]}
        if "collectives" in m:
            side["collectives"] = {"calls": m["collectives"]["calls"],
                                   "bytes": m["collectives"]["bytes"]}
        if "mesh" in m:
            side["mesh"] = m["mesh"]
        return side, outs

    base, out1 = run(None)
    mesh = ServingMesh.make(tp=tp, collective=coll,
                            devices=devices[:tp])
    shard, outN = run(mesh)
    matches = [bool(np.array_equal(a, b)) for a, b in zip(out1, outN)]
    tok_eq = sum(int(np.count_nonzero(a == b)) for a, b in
                 zip(out1, outN) if a.shape == b.shape)
    tok_all = sum(a.size for a in out1)
    f50 = shard["decode_step_ms"].get("p50")
    u50 = base["decode_step_ms"].get("p50")
    return {"metric": "serving_tp_greedy_parity",
            "value": round(sum(matches) / max(len(matches), 1), 4),
            "unit": "fraction of requests with identical greedy output",
            "token_match": round(tok_eq / max(tok_all, 1), 6),
            "collective": coll, "tp": tp,
            "platform": devices[0].platform,
            "tp1": base, f"tp{tp}": shard,
            **({"decode_step_p50_ratio": round(f50 / u50, 3)}
               if f50 and u50 else {}),
            "requests": R, "capacity": cap, "ctx": ctx, "gen": gen_n,
            "arrival_rate_hz": rate}


def bench_serving_disagg():
    """Colocated vs DISAGGREGATED serving A/B over the default
    backend's devices under a PREFILL-HEAVY Poisson mix (long prompts, short
    decodes — the workload where one prefill chunk stalls every
    in-flight decode slot on a colocated engine). Same arrival trace
    through a colocated ServingEngine and a DisaggregatedEngine
    (1-device prefill group + 1-device decode group by default); banks
    greedy parity, TTFT/TPOT/decode_step_ms distributions for both
    sides, the colocated DECODE-CONTENTION count (steps that ran a
    prefill chunk while decode slots were live — each one a decode
    stall the split removes), and the KV-handoff bytes/latency the
    disaggregated side pays instead."""
    from paddle_tpu.distributed.dryrun import resolve_devices

    pre_tp = int(os.environ.get("BENCH_DISAGG_PREFILL_TP", "1"))
    dec_tp = int(os.environ.get("BENCH_DISAGG_DECODE_TP", "1"))
    coll = os.environ.get("BENCH_DISAGG_COLLECTIVE", "gather")
    devices = resolve_devices(max(pre_tp + dec_tp, 2))

    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference import (DisaggregatedEngine,
                                      GenerationConfig, ServingEngine)
    from paddle_tpu.models.llama import LlamaConfig, init_params

    cap = int(os.environ.get("BENCH_DISAGG_CAPACITY", "4"))
    R = int(os.environ.get("BENCH_DISAGG_REQUESTS", str(4 * cap)))
    ctx = int(os.environ.get("BENCH_DISAGG_CTX", "96"))
    gen_n = int(os.environ.get("BENCH_DISAGG_GEN", "12"))
    rate = float(os.environ.get("BENCH_DISAGG_RATE_HZ", "16.0"))
    hidden = int(os.environ.get("BENCH_DISAGG_HIDDEN", "128"))
    layers = int(os.environ.get("BENCH_DISAGG_LAYERS", "4"))
    cfg = LlamaConfig(vocab_size=8192, hidden_size=hidden,
                      intermediate_size=hidden * 4,
                      num_hidden_layers=layers,
                      num_attention_heads=hidden // 32,
                      num_key_value_heads=hidden // 32,
                      max_position_embeddings=ctx + gen_n,
                      dtype=jnp.float32, remat=False)
    with jax.default_device(devices[0]):
        params = init_params(cfg, jax.random.PRNGKey(0),
                             dtype=jnp.float32)
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, 8192, (R, ctx)).astype(np.int32)
    gaps = rng.exponential(1.0 / rate, R)
    gaps[0] = 0.0
    arrivals = np.cumsum(gaps)
    g = GenerationConfig(max_new_tokens=gen_n, greedy=True)
    buckets = (32, ctx)

    def run(make):
        eng = make()
        eng.submit(prompts[0], GenerationConfig(max_new_tokens=2,
                                                greedy=True))
        eng.drain()                  # compile outside the window
        eng.reset_metrics()
        t0, i, reqs = time.perf_counter(), 0, []
        contended = 0
        is_coloc = isinstance(eng, ServingEngine)
        while i < R or not eng.idle:
            now = time.perf_counter() - t0
            while i < R and arrivals[i] <= now:
                reqs.append(eng.submit(prompts[i], g))
                i += 1
            if is_coloc:
                pc0 = eng.counters["prefill_chunks"]
                ds0 = eng.counters["decode_steps"]
                ran = eng.step()
                # a step that ran BOTH a prefill chunk and a decode
                # dispatch serialized the decode behind the chunk on
                # the same chips: one counted decode stall
                if (eng.counters["prefill_chunks"] > pc0
                        and eng.counters["decode_steps"] > ds0):
                    contended += 1
            else:
                ran = eng.step()
            if not ran and i < R:
                time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
        wall = time.perf_counter() - t0
        m = eng.metrics()
        return m, wall, [r.output_ids for r in reqs], contended

    def mk_coloc():
        return ServingEngine(params, cfg, capacity=cap, block_size=16,
                             max_seq_len=ctx + gen_n,
                             prefill_buckets=buckets,
                             observability=True)

    def mk_disagg():
        return DisaggregatedEngine(
            params, cfg, prefill_devices=devices[:pre_tp],
            decode_devices=devices[pre_tp:pre_tp + dec_tp],
            collective=coll, capacity=cap, prefill_slots=2,
            block_size=16, max_seq_len=ctx + gen_n,
            prefill_buckets=buckets, observability=True)

    coloc_m, coloc_wall, coloc_out, contended = run(mk_coloc)
    dis_m, dis_wall, dis_out, _ = run(mk_disagg)
    matches = [bool(np.array_equal(a, b))
               for a, b in zip(coloc_out, dis_out)]
    dec = dis_m["groups"]["decode"]
    side = lambda m, w: {                                # noqa: E731
        "tokens_per_sec": round(R * gen_n / w, 1),
        "ttft_ms": m["latency"]["ttft_ms"],
        "tpot_ms": m["latency"]["tpot_ms"]}
    return {"metric": "serving_disagg_greedy_parity",
            "value": round(sum(matches) / max(len(matches), 1), 4),
            "unit": "fraction of requests with identical greedy output",
            "platform": devices[0].platform,
            "colocated": {**side(coloc_m, coloc_wall),
                          "decode_step_ms":
                              coloc_m["latency"]["decode_step_ms"],
                          "decode_contended_steps": contended,
                          "decode_steps": coloc_m["decode_steps"]},
            "disaggregated": {
                **side(dis_m, dis_wall),
                "decode_step_ms":
                    dec["latency"]["decode_step_ms"],
                "decode_steps": dec["decode_steps"],
                "handoffs": dis_m["handoffs"],
                "handoff_ms": dis_m["latency"]["handoff_ms"],
                "kv_bytes_transferred": dis_m["kv_bytes_transferred"],
                "handoff_traces": dis_m["handoff_traces"],
                "retrace_warnings": dis_m["retrace_warnings"]},
            "prefill_tp": pre_tp, "decode_tp": dec_tp,
            "collective": coll,
            "requests": R, "capacity": cap, "ctx": ctx, "gen": gen_n,
            "arrival_rate_hz": rate}


def bench_serving_fleet():
    """Fleet serving A/B: N prefix-cached replicas (host-RAM KV
    offload on, pools deliberately undersized so eviction pressure
    spills) behind the ServingFleet router, over a Poisson arrival
    stream whose prompts share ZIPF-distributed prefixes (a few hot
    system prompts, a long tail — the real traffic shape). The SAME
    trace runs three ways: prefix-aware routing, round-robin routing
    (the naive baseline the prefix router must beat on warm-hit
    ratio), and one monolithic colocated engine (the greedy-parity
    reference and the single-engine throughput anchor). Banks the
    router warm-hit ratio and the replica-cache hit ratio for both
    policies, TTFT/TPOT distributions, spill/restore pages+bytes
    through the offload tier, and the parity fraction."""
    import jax
    from paddle_tpu.inference import (GenerationConfig, ServingEngine,
                                      ServingFleet)
    from paddle_tpu.models.llama import LlamaConfig, init_params

    N = int(os.environ.get("BENCH_FLEET_REPLICAS", "2"))
    cap = int(os.environ.get("BENCH_FLEET_CAPACITY", "2"))
    R = int(os.environ.get("BENCH_FLEET_REQUESTS", str(12 * N)))
    pref = int(os.environ.get("BENCH_FLEET_PREFIX", "48"))
    tail = int(os.environ.get("BENCH_FLEET_TAIL", "16"))
    gen_n = int(os.environ.get("BENCH_FLEET_GEN", "8"))
    P = int(os.environ.get("BENCH_FLEET_TEMPLATES", "4"))
    zipf_a = float(os.environ.get("BENCH_FLEET_ZIPF_A", "1.2"))
    rate = float(os.environ.get("BENCH_FLEET_RATE_HZ", "16.0"))
    hidden = int(os.environ.get("BENCH_FLEET_HIDDEN", "128"))
    layers = int(os.environ.get("BENCH_FLEET_LAYERS", "4"))
    ctx = pref + tail
    BS = 16

    import jax.numpy as jnp
    cfg = LlamaConfig(vocab_size=8192, hidden_size=hidden,
                      intermediate_size=hidden * 4,
                      num_hidden_layers=layers,
                      num_attention_heads=hidden // 32,
                      num_key_value_heads=hidden // 32,
                      max_position_embeddings=ctx + gen_n,
                      dtype=jnp.float32, remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0),
                         dtype=jnp.float32)
    rng = np.random.RandomState(0)
    templates = [rng.randint(0, 8192, (pref,)) for _ in range(P)]
    # Zipf template popularity, clipped to the template pool
    picks = np.minimum(rng.zipf(zipf_a, R) - 1, P - 1)
    prompts = [np.concatenate([templates[int(k)],
                               rng.randint(0, 8192, (tail,))])
               .astype(np.int32) for k in picks]
    gaps = rng.exponential(1.0 / rate, R)
    gaps[0] = 0.0
    arrivals = np.cumsum(gaps)
    g = GenerationConfig(max_new_tokens=gen_n, greedy=True)
    req_pages = -(-(ctx + gen_n) // BS)

    def mk_replica():
        # pool = live requests + ~1.5 cached prompts: the Zipf tail
        # forces eviction pressure, so the offload tier actually spills
        return ServingEngine(
            params, cfg, capacity=cap, block_size=BS,
            max_seq_len=ctx + gen_n,
            num_blocks=(cap + 1) * req_pages + req_pages // 2 + 1,
            prefill_buckets=(tail, ctx), prefix_cache=True,
            kv_offload=True, observability=True)

    def run_fleet(policy):
        reps = [mk_replica() for _ in range(N)]
        warm = GenerationConfig(max_new_tokens=2, greedy=True)
        wtail = rng.randint(0, 8192, (tail,))
        for eng in reps:
            # compile BOTH buckets on every replica outside the window
            # (full-prompt ctx bucket, then a warm hit sharing the
            # SAME template so the suffix tail bucket runs too)
            eng.submit(prompts[0][:ctx], warm)
            eng.drain()
            eng.submit(np.concatenate([prompts[0][:pref], wtail])
                       .astype(np.int32), warm)
            eng.drain()
        # BENCH_TELEMETRY=0 opts out of the continuous telemetry plane
        tel = os.environ.get("BENCH_TELEMETRY", "1") != "0"
        fleet = ServingFleet(reps, policy=policy, observability=True,
                             telemetry=tel)
        fleet.reset_metrics()
        t0, i = time.perf_counter(), 0
        reqs = []
        while i < R or not fleet.idle:
            now = time.perf_counter() - t0
            while i < R and arrivals[i] <= now:
                reqs.append(fleet.submit(prompts[i], g))
                i += 1
            if not fleet.step() and i < R:
                time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
        wall = time.perf_counter() - t0
        if fleet.telemetry is not None and policy == "prefix":
            # bank the per-replica series/alert log for the headline
            # policy (tools/telemetry_summary.py reads it)
            fleet.telemetry.write_jsonl(os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_FLEET_TELEMETRY.jsonl"))
        return fleet.metrics(), wall, [r.output_ids for r in reqs]

    def run_mono():
        blocks = (N * cap + P + 1) * req_pages + 1
        eng = ServingEngine(params, cfg, capacity=N * cap,
                            block_size=BS, max_seq_len=ctx + gen_n,
                            num_blocks=blocks,
                            prefill_buckets=(tail, ctx),
                            prefix_cache=True, observability=True)
        warm = GenerationConfig(max_new_tokens=2, greedy=True)
        eng.submit(prompts[0][:ctx], warm)
        eng.drain()
        eng.submit(np.concatenate(
            [prompts[0][:pref], rng.randint(0, 8192, (tail,))])
            .astype(np.int32), warm)
        eng.drain()
        eng.reset_metrics()
        t0, i = time.perf_counter(), 0
        reqs = []
        while i < R or not eng.idle:
            now = time.perf_counter() - t0
            while i < R and arrivals[i] <= now:
                reqs.append(eng.submit(prompts[i], g))
                i += 1
            if not eng.step() and i < R:
                time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
        wall = time.perf_counter() - t0
        return eng.metrics(), wall, [r.output_ids for r in reqs]

    def cache_hit_ratio(m):
        hits = miss = 0
        for rm in m["replicas"].values():
            pc = rm.get("prefix_cache", {})
            hits += pc.get("hits", 0)
            miss += pc.get("misses", 0)
        return round(hits / max(hits + miss, 1), 4)

    pfx_m, pfx_wall, pfx_out = run_fleet("prefix")
    rr_m, rr_wall, rr_out = run_fleet("round_robin")
    mono_m, mono_wall, mono_out = run_mono()
    matches = [bool(np.array_equal(a, b))
               for a, b in zip(mono_out, pfx_out)]
    side = lambda m, w: {                               # noqa: E731
        "tokens_per_sec": round(R * gen_n / w, 1),
        "ttft_ms": m["latency"]["ttft_ms"],
        "tpot_ms": m["latency"]["tpot_ms"],
        "retrace_warnings": m["retrace_warnings"]}
    return {
        "metric": "serving_fleet_warm_hit_ratio",
        "value": pfx_m["routing"]["warm_hit_ratio"],
        "unit": "fraction of requests routed onto their warm replica",
        "platform": jax.devices()[0].platform,
        "greedy_parity_vs_monolithic": round(
            sum(matches) / max(len(matches), 1), 4),
        "prefix_routing": {
            **side(pfx_m, pfx_wall),
            "warm_hit_ratio": pfx_m["routing"]["warm_hit_ratio"],
            "cache_hit_ratio": cache_hit_ratio(pfx_m),
            "diverted": pfx_m["routing"]["diverted"],
            "offload": pfx_m["offload"]},
        **({"telemetry_alerts": pfx_m["telemetry"]["alerts"]}
           if "telemetry" in pfx_m else {}),
        "round_robin": {
            **side(rr_m, rr_wall),
            "warm_hit_ratio": rr_m["routing"]["warm_hit_ratio"],
            "cache_hit_ratio": cache_hit_ratio(rr_m),
            "offload": rr_m["offload"]},
        "monolithic": side(mono_m, mono_wall),
        "replicas": N, "capacity_per_replica": cap, "requests": R,
        "templates": P, "zipf_a": zipf_a, "prefix": pref,
        "tail": tail, "gen": gen_n, "arrival_rate_hz": rate}


def bench_sd_unet(steps=8, batch=4):
    """BASELINE config 6: Stable-Diffusion-class UNet denoise step,
    compiled (SD-1.x geometry at 64x64 latents)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.unet import UNetConfig, UNetModel

    paddle.seed(0)
    sd_cfg = UNetConfig(model_channels=192, channel_mult=(1, 2, 4, 4),
                        num_res_blocks=2, attention_levels=(1, 2, 3),
                        num_heads=8, context_dim=768)
    net = UNetModel(sd_cfg)
    net.eval()
    pure_fn, params, buffers = net.functional()
    import jax
    import jax.numpy as jnp

    @jax.jit
    def denoise(params, buffers, x, t, ctx):
        out, _ = pure_fn(params, buffers, x, t, ctx)
        return out

    x = jnp.asarray(np.random.randn(batch, 4, 64, 64), jnp.float32)
    t = jnp.asarray(np.random.randint(0, 1000, (batch,)), jnp.int32)
    ctx = jnp.asarray(np.random.randn(batch, 77, 768), jnp.float32)
    out = denoise(params, buffers, x, t, ctx)
    np.asarray(out[0, 0, 0, :2])  # compile + host sync
    t0 = time.perf_counter()
    for _ in range(steps):
        out = denoise(params, buffers, x, t, ctx)
    np.asarray(out[0, 0, 0, :2])  # host sync
    dt = time.perf_counter() - t0
    return {"metric": "sd_unet_denoise_steps_per_sec_per_chip",
            "value": round(steps * batch / dt, 2), "unit": "imgs-steps/sec",
            "batch": batch}


def bench_resnet_breakdown(batch=None):
    """Round-3 verdict Next #3: the perf number must come with a
    bottleneck analysis. Decomposes the ResNet train step into
    host->device transfer, forward, forward+backward, and the full
    donated train step (forward+backward+optimizer), each compiled and
    timed separately; also saves an XPlane trace of 3 full steps."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import resnet50

    if batch is None:
        batch = int(os.environ.get("BENCH_BREAKDOWN_BATCH", "256"))
    paddle.seed(0)
    net = resnet50(num_classes=1000)
    net.train()
    opt = paddle.optimizer.Momentum(0.1, parameters=net.parameters())
    ts = paddle.jit.train_step(net, F.cross_entropy, opt,
                               amp_level="O1", amp_dtype="bfloat16")
    xh = np.random.randn(batch, 3, 224, 224).astype(np.float32)
    yh = np.random.randint(0, 1000, batch)

    res = {"metric": "resnet50_step_breakdown", "batch": batch}

    def timed(fn, steps=10):
        return _timed_host_synced(fn, steps)

    # host->device transfer of one batch (sync: tiny device->host read)
    res["h2d_ms"] = timed(lambda: jax.device_put(xh), steps=5)

    x = paddle.to_tensor(xh)
    y = paddle.to_tensor(yh)
    pure_fn, params, buffers = net.functional()

    # fwd/bwd sub-measurements mirror the AMP-O1 bf16 data path (params
    # and activations bf16, loss fp32) so the residual against the full
    # bf16 train step isolates the optimizer update
    params16 = jax.tree_util.tree_map(
        lambda v: v.astype(jnp.bfloat16)
        if jnp.issubdtype(v.dtype, jnp.floating) else v, params)
    fwd = jax.jit(lambda p, b, v: pure_fn(p, b, v)[0])
    xv = jax.device_put(jnp.asarray(xh, jnp.bfloat16))
    res["forward_ms"] = timed(lambda: fwd(params16, buffers, xv))

    yv = jax.device_put(jnp.asarray(yh, jnp.int32))

    def loss_fn(p, b, v, t):
        import jax.nn as jnn
        logits = pure_fn(p, b, v)[0].astype(jnp.float32)
        lp = jnn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, t[:, None], 1))

    fb = jax.jit(lambda p, b, v, t: jax.grad(loss_fn)(p, b, v, t))
    res["fwd_bwd_ms"] = timed(lambda: fb(params16, buffers, xv, yv))

    res["full_step_ms"] = timed(lambda: ts(x, y)._value)
    res["imgs_per_sec"] = round(batch / (res["full_step_ms"] / 1e3), 1)
    # residual of the full AMP step over bf16 fwd+bwd: optimizer update
    # + AMP bookkeeping (approximate — separate programs fuse differently)
    res["optimizer_residual_ms"] = round(
        res["full_step_ms"] - res["fwd_bwd_ms"], 2)

    # ingest overlap: fresh host batch every step, (a) synchronous h2d
    # inline (step = transfer + compute) vs (b) the double-buffered
    # _DevicePrefetchIter (steady state = max(transfer, compute)).
    # Where transfer dominates (b) ≈ h2d_ms while (a) ≈ h2d_ms +
    # full_step_ms; where compute does, (b) ≈ compute.
    try:
        from paddle_tpu.io.dataloader import _DevicePrefetchIter
        n_ing, t_sync = 4, time.perf_counter()
        for _ in range(n_ing):
            loss = ts(paddle.to_tensor(xh), paddle.to_tensor(yh))
        float(loss)
        res["ingest_sync_step_ms"] = round(
            (time.perf_counter() - t_sync) / n_ing * 1e3, 2)
        pf = _DevicePrefetchIter(
            iter([(xh, yh)] * (n_ing + 2)),
            lambda b: (paddle.to_tensor(b[0]), paddle.to_tensor(b[1])),
            depth=2)
        loss = ts(*next(pf))  # first pull pays its own transfer
        float(loss)
        t_pf = time.perf_counter()
        for _ in range(n_ing):
            loss = ts(*next(pf))
        float(loss)
        res["ingest_prefetch_step_ms"] = round(
            (time.perf_counter() - t_pf) / n_ing * 1e3, 2)
        pf.close()
        res["ingest_overlap_speedup"] = round(
            res["ingest_sync_step_ms"]
            / max(res["ingest_prefetch_step_ms"], 1e-6), 2)
    except Exception as e:  # noqa: BLE001 — breakdown leg is best-effort
        res["ingest_error"] = f"{type(e).__name__}: {e}"[:160]

    try:
        trace_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "profile_resnet")
        with jax.profiler.trace(trace_dir):
            for _ in range(3):
                out = ts(x, y)
            jax.block_until_ready(out._value)
        res["xplane_trace"] = trace_dir
    except Exception as e:  # noqa: BLE001 — trace is best-effort
        res["xplane_error"] = f"{type(e).__name__}: {e}"[:120]
    return res


def bench_ppyoloe(steps=10, batch=8, size=640):
    """BASELINE config 5: PP-YOLOE-s detection, the full backbone ->
    neck -> head -> device-side NMS pipeline as ONE compiled XLA
    program (no host round-trip; round-3 verdict weak #5). Throughput
    in imgs/sec at the standard 640x640 eval shape. vs_baseline is the
    PP-YOLOE paper's 208 FPS (V100 TensorRT FP16, batch 1) — the only
    published reference number for this config."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.vision.models.ppyoloe import ppyoloe_s
    from paddle_tpu.vision.nms_device import ppyoloe_postprocess

    batch = int(os.environ.get("BENCH_YOLO_BATCH", batch))
    net = ppyoloe_s(num_classes=80)
    net.eval()
    pure_fn, params, buffers = net.functional()
    params = jax.tree_util.tree_map(
        lambda v: v.astype(jnp.bfloat16)
        if jnp.issubdtype(v.dtype, jnp.floating) else v, params)

    @jax.jit
    def detect(params, buffers, images):
        (scores, boxes), _ = pure_fn(params, buffers, images)
        return ppyoloe_postprocess(scores.astype(jnp.float32),
                                   boxes.astype(jnp.float32),
                                   score_threshold=0.05,
                                   iou_threshold=0.6, max_dets=100)

    imgs = jnp.asarray(np.random.RandomState(0)
                       .randn(batch, 3, size, size), jnp.bfloat16)
    ms = _timed_host_synced(lambda: detect(params, buffers, imgs),
                            steps=steps)
    ips = batch / (ms / 1e3)
    return {"metric": "ppyoloe_s_detect_imgs_per_sec_per_chip",
            "value": round(ips, 2), "unit": "imgs/sec/chip",
            "vs_baseline": round(ips / 208.0, 4), "batch": batch,
            "size": size}


def bench_flash_tune():
    """Eagerly sweep Pallas flash-attention block candidates at the
    attention shapes of the llama/bert bench configs and persist the
    winners (~/.cache/paddle_tpu/autotune.json). Tuning can only run on
    EAGER calls (it cannot time while tracing); traced calls — i.e. the
    jitted train steps — then read the tuned blocks from the cache
    (ops/pallas/flash_attention.py:_tuned_blocks). Run this BEFORE the
    llama config so its rungs pick tuned blocks."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.flags import GLOBAL_FLAGS
    from paddle_tpu.ops.pallas.autotune import _cache
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas

    from paddle_tpu.ops.pallas._util import interpret_mode
    if interpret_mode():
        # off-TPU the sweep is meaningless (and interpret-running a
        # 2048-seq flash kernel takes minutes)
        return {"metric": "flash_autotune_shapes", "value": 0,
                "unit": "shapes swept", "skipped": "interpret mode"}
    GLOBAL_FLAGS.set("kernel_autotune", True)
    # (B, S, H, KV, D) of every llama rung (hidden 2048 -> 16 heads,
    # 1536 -> 12, 1024 -> 8), the LLAMA_LADDER top rungs (3072 -> 24,
    # 4096 -> 32) and the ernie decode prefill
    shapes = [(4, 2048, 16, 16, 128), (2, 2048, 16, 16, 128),
              (1, 2048, 16, 16, 128), (8, 2048, 12, 12, 128),
              (4, 2048, 12, 12, 128), (2, 2048, 8, 8, 128),
              (4, 2048, 24, 24, 128), (2, 2048, 32, 32, 128),
              (1, 2048, 32, 32, 128), (8, 1024, 16, 16, 64)]
    tuned = {}
    key = jax.random.PRNGKey(0)
    for B, S, H, KV, D in shapes:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, S, KV, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, S, KV, D), jnp.bfloat16)
        try:
            out = flash_attention_pallas(q, k, v, causal=True)
            jax.block_until_ready(out)
            from paddle_tpu.ops.pallas.flash_attention import (
                autotune_cache_key)
            ck = autotune_cache_key(B * H, S, S, B * KV, D, True,
                                    q.dtype)
            tuned[f"{B}x{S}x{H}x{D}"] = _cache.get(ck)
        except Exception as e:  # noqa: BLE001
            tuned[f"{B}x{S}x{H}x{D}"] = f"{type(e).__name__}: {e}"[:120]

    # decode-path tunables (pages per loop iteration for the paged
    # attention kernel, block_f for the fused MLP): the serving read
    # sites are all TRACED (the jitted chunk runner / engine decode fn)
    # and can only READ the persistent table — this eager sweep is what
    # writes it, exactly like flash's above. The paged kernel sweeps at
    # the serving_engine/llama bench shapes; the fused MLP sweeps where
    # registry dispatch selects it (a direct eager call past the VMEM
    # budget would just VMEM-OOM the compiler, sweeping a key no traced
    # program ever reads).
    from paddle_tpu.ops.pallas.fused_decode_block import (
        decode_meta_dims, fused_mlp_block_pallas)
    from paddle_tpu.ops.pallas.registry import KERNELS
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention_decode_pallas)
    decode_tuned = {}
    key = jax.random.PRNGKey(1)

    def _sweep(name, fn):
        try:
            jax.block_until_ready(fn())
            decode_tuned[name] = "swept"
        except Exception as e:  # noqa: BLE001
            decode_tuned[name] = f"{type(e).__name__}: {e}"[:120]

    BS = 16
    # MB keys the autotune cache, so sweep BOTH page-count classes the
    # bench readers trace with: generate_paged's static baseline packs
    # exactly ceil((ctx+gen)/BS) pages per sequence, while the
    # ServingEngine's table adds a prefill-bucket of slack
    # (serving.py max_blocks) — derived from the same env knobs
    # bench_serving_engine reads so they cannot drift apart silently
    s_ctx = int(os.environ.get("BENCH_SERVE_CTX", "256"))
    s_gen = int(os.environ.get("BENCH_SERVE_GEN", "64"))
    MBs = sorted({-(-(s_ctx + s_gen) // BS),
                  -(-(s_ctx + s_gen + s_ctx) // BS)})
    # B/H/KV/hd also key the table: alongside the fixed generic rows,
    # sweep the exact shape class bench_serving_engine's traced
    # readers will look up (capacity/heads from the same env knobs;
    # its LlamaConfig rides the default bf16 with hd fixed at 64)
    rows = [(jnp.float32, 8, 16, 16, 64),
            (jnp.float32, 8, 16, 16, 128),
            (jnp.float32, 8, 8, 8, 64),
            (jnp.bfloat16, 8, 16, 16, 64)]
    s_cap = int(os.environ.get("BENCH_SERVE_CAPACITY", "8"))
    s_heads = int(os.environ.get("BENCH_SERVE_HIDDEN", "1024")) // 64
    serving_row = (jnp.bfloat16, s_cap, s_heads, s_heads, 64)
    if serving_row not in rows:
        rows.append(serving_row)
    for dt, B, H, KV, hd in rows:
        D = H * hd
        ks = jax.random.split(key, 11)
        x = jax.random.normal(ks[3], (B, D), dt)
        nw = jnp.ones((D,), dt)
        wq = jax.random.normal(ks[4], (D, H * hd), dt) * 0.02
        wk = jax.random.normal(ks[5], (D, KV * hd), dt) * 0.02
        wv = jax.random.normal(ks[6], (D, KV * hd), dt) * 0.02
        wo = jax.random.normal(ks[7], (H * hd, D), dt) * 0.02
        for MB in MBs:
            T = BS * MB
            q = jax.random.normal(ks[0], (B, H, hd), dt)
            kp = jax.random.normal(ks[1], (B * MB, BS, KV, hd), dt)
            vp = jax.random.normal(ks[2], (B * MB, BS, KV, hd), dt)
            bt = jnp.arange(B * MB, dtype=jnp.int32).reshape(B, MB)
            sl = jnp.full((B,), T - 2, jnp.int32)
            tag = f"{B}x{H}x{KV}x{hd}x{jnp.dtype(dt).name}xMB{MB}"
            _sweep(f"paged_decode|{tag}",
                   lambda: paged_attention_decode_pallas(q, kp, vp,
                                                         bt, sl))
        wg = jax.random.normal(ks[8], (D, 4 * D), dt) * 0.02
        wu = jax.random.normal(ks[9], (D, 4 * D), dt) * 0.02
        wd = jax.random.normal(ks[10], (4 * D, D), dt) * 0.02
        _sweep(f"fused_mlp|{B}x{H}x{KV}x{hd}x{jnp.dtype(dt).name}",
               lambda: fused_mlp_block_pallas(x, nw, wg, wu, wd))
        # quantized-WEIGHT tunables (r18): int8/int4 tiles are their
        # own autotune shape classes (distinct cache keys) — sweep
        # ONLY where registry dispatch selects the Pallas variant
        # under the weight_dtype meta, like every guard above
        from paddle_tpu.quantization import ptq as _ptq
        for wq_name, wq_bits in (("int8", 8), ("int4", 4)):
            tag = (f"{B}x{H}x{KV}x{hd}x{jnp.dtype(dt).name}"
                   f"x{wq_name}w")
            mq = decode_meta_dims(B, D, 4 * D, dt, weight_dtype=wq_name)
            if KERNELS.dispatch("decode_mlp_block", mq)[0] \
                    != "pallas_fused":
                decode_tuned[f"fused_mlp_{wq_name}w|{tag}"] = \
                    "skipped: dispatch -> unfused"
            else:
                _sweep(f"fused_mlp_{wq_name}w|{tag}",
                       lambda: fused_mlp_block_pallas(
                           x, nw, _ptq.quantize_leaf(wg, wq_bits),
                           _ptq.quantize_leaf(wu, wq_bits),
                           _ptq.quantize_leaf(wd, wq_bits,
                                              pack_axis=1)))
        # fused-prefill tunables ((block_q, pages_per_step) pairs) at
        # the serving bucket widths — the engine's chunk runners are
        # traced and only READ the table; dispatch-guarded like the
        # decode sweeps (a rejected shape's key is never looked up)
        from paddle_tpu.ops.pallas.fused_prefill_block import (
            fused_prefill_attn_pallas, prefill_meta_dims)
        for P in (32, 64):
            MBp = MBs[-1]
            pm = prefill_meta_dims(P, D, H, KV, hd, 4 * D, BS, MBp,
                                   dt, dt, False)
            sel_name, _ = KERNELS.dispatch("prefill_attn_block", pm)
            ptag = f"{P}x{H}x{KV}x{hd}x{jnp.dtype(dt).name}xMB{MBp}"
            if sel_name != "pallas_fused":
                decode_tuned[f"fused_prefill|{ptag}"] = \
                    f"skipped: dispatch -> {sel_name}"
                continue
            T2 = BS * MBp
            pos0 = min(T2 - P, T2 // 2)
            kpp = jax.random.normal(ks[1], (B * MBp, BS, KV, hd), dt)
            vpp = jax.random.normal(ks[2], (B * MBp, BS, KV, hd), dt)
            ptab = jnp.arange(MBp, dtype=jnp.int32)
            pang = ((pos0 + np.arange(P))[:, None]
                    / (10000.0 ** (np.arange(0, hd, 2) / hd)))
            psin = jnp.asarray(np.sin(pang), jnp.float32)
            pcos = jnp.asarray(np.cos(pang), jnp.float32)
            xp = jax.random.normal(ks[3], (P, D), dt)
            _sweep(f"fused_prefill|{ptag}",
                   lambda: fused_prefill_attn_pallas(
                       xp, nw, wq, wk, wv, wo, psin, pcos, kpp, vpp,
                       ptab, jnp.int32(pos0), jnp.int32(P))[0])
    # training-path tunables (fused linear+CE (block_t, block_v) and
    # fused-SwiGLU block_f): the read sites are the jitted train steps
    # (models/llama.py, models/gpt.py loss_fn) — traced, so they can
    # only READ the persistent table; this eager sweep writes it. Each
    # sweep times the full fwd+bwd the trainer runs (the kernels'
    # resolve_candidate builders do), at the exact (T, D, V) shape
    # classes the llama/gpt bench rungs trace with — derived from the
    # same defaults bench_llama/bench_gpt use so the keys cannot drift
    # from the traced readers'. Shapes are swept ONLY where registry
    # dispatch selects the Pallas variant (a direct eager call past the
    # VMEM budget would sweep a key no traced program ever reads).
    from paddle_tpu.ops.pallas.fused_train import (
        ce_meta, linear_ce_pallas, swiglu_meta, swiglu_pallas)
    train_tuned = {}
    key = jax.random.PRNGKey(2)
    # (batch, seq, hidden, vocab, inter): the default llama bench rung
    # + the LLAMA_LADDER rungs' loss shapes (hidden 1536/1024 rungs
    # share vocab 32000); gpt rides the llama (B*S, D, V) shape class
    tshapes = [(2, 2048, 2048, 32000, 5504),
               (8, 2048, 1536, 32000, 4096),
               (2, 2048, 1024, 32000, 2816)]
    for B, S, D, V, F in tshapes:
        T = B * S
        ks = jax.random.split(key, 4)
        dt = jnp.bfloat16
        tag = f"{T}x{D}x{V}x{jnp.dtype(dt).name}"
        sel, _ = KERNELS.dispatch("fused_linear_ce", ce_meta(T, D, V, dt))
        if sel != "pallas_fused":
            train_tuned[f"linear_ce|{tag}"] = f"skipped: dispatch -> {sel}"
        else:
            x = jax.random.normal(ks[0], (T, D), dt) * 0.05
            hw = jax.random.normal(ks[1], (D, V), dt) * 0.02
            lb = jnp.asarray(
                np.random.RandomState(0).randint(0, V, (T,)), jnp.int32)
            try:
                _, grads = jax.value_and_grad(
                    lambda a, h: linear_ce_pallas(a, h, lb),
                    argnums=(0, 1))(x, hw)
                jax.block_until_ready(grads)
                train_tuned[f"linear_ce|{tag}"] = "swept"
            except Exception as e:  # noqa: BLE001
                train_tuned[f"linear_ce|{tag}"] = \
                    f"{type(e).__name__}: {e}"[:120]
        stag = f"{T}x{F}x{jnp.dtype(dt).name}"
        sel, _ = KERNELS.dispatch("fused_swiglu", swiglu_meta(T, F, dt))
        if sel != "pallas_fused":
            train_tuned[f"swiglu|{stag}"] = f"skipped: dispatch -> {sel}"
        else:
            g = jax.random.normal(ks[2], (T, F), dt)
            u = jax.random.normal(ks[3], (T, F), dt)
            try:
                _, grads = jax.value_and_grad(
                    lambda a, b: swiglu_pallas(a, b).astype(
                        jnp.float32).sum(), argnums=(0, 1))(g, u)
                jax.block_until_ready(grads)
                train_tuned[f"swiglu|{stag}"] = "swept"
            except Exception as e:  # noqa: BLE001
                train_tuned[f"swiglu|{stag}"] = \
                    f"{type(e).__name__}: {e}"[:120]
    return {"metric": "flash_autotune_shapes", "value": len(shapes),
            "unit": "shapes swept", "winners": tuned,
            "decode_tunables": decode_tuned,
            "train_tunables": train_tuned}


def bench_kernels():
    """VERDICT round-2 item: run the Pallas pack COMPILED on the real chip
    (not interpret mode) — numerics vs the XLA composition plus a
    microbench of each. On a non-TPU backend (interpret mode) shapes are
    shrunk and timing skipped: the numbers would mean nothing."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas._util import interpret_mode
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention_decode_pallas)
    from paddle_tpu.ops.pallas.fused_adamw import fused_adamw
    from paddle_tpu.ops.pallas.norms import (layer_norm_pallas,
                                             residual_rms_norm_pallas,
                                             residual_rms_norm_ref,
                                             rms_norm_pallas)

    interp = interpret_mode()
    res = {"interpret": bool(interp),
           "platform": jax.devices()[0].platform,
           "repro": _repro_meta(), "cases": {}}
    key = jax.random.PRNGKey(0)

    roofline_on = os.environ.get("BENCH_ROOFLINE", "1").lower() \
        not in ("0", "false")
    if roofline_on:
        from paddle_tpu.analysis.kernel_catalog import modeled_flops
        from paddle_tpu.analysis.kernel_rules import modeled_launch_bytes
        from paddle_tpu.observability.roofline import roofline_point
        from paddle_tpu.ops.pallas._util import capture_kernel_launches

    def timed(fn, *args, steps=20):
        out = jax.block_until_ready(fn(*args))  # compile
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / steps * 1e6  # us

    def record(name, pallas_fn, ref_fn, *args, tol, flops=None,
               bytes_moved=None):
        """flops / bytes_moved (per call) turn the relative speedup into
        ABSOLUTE utilization: mfu = flops/time/peak_flops, bw_frac =
        bytes/time/peak_HBM_bw (VERDICT r4 weak #4 — 'fast' must be
        measured against the hardware roofline, not a jnp baseline; the
        CUDA library kernel behind the reference's
        phi/kernels/gpu/flash_attn_kernel.cu:517 is ~60% MFU class)."""
        try:
            # roofline pricing rides the SAME traced program, captured
            # via eval_shape BEFORE the first real call (jit caching
            # would skip tracing afterwards) — modeled bytes/FLOPs from
            # the cost model, not the hand bytes_moved estimates
            rspecs = []
            if roofline_on:
                try:
                    with capture_kernel_launches() as rspecs:
                        jax.eval_shape(pallas_fn, *args)
                except Exception:  # noqa: BLE001 — pricing is optional
                    rspecs = []
            got = np.asarray(jax.block_until_ready(pallas_fn(*args)),
                             np.float32)
            want = np.asarray(jax.block_until_ready(ref_fn(*args)),
                              np.float32)
            err = float(np.max(np.abs(got - want)))
            case = {"max_err": round(err, 5), "ok": err < tol}
            us_p = None
            if not interp:
                us_p = timed(pallas_fn, *args)
                us_x = timed(ref_fn, *args)
                case.update(us_pallas=round(us_p, 1), us_xla=round(us_x, 1),
                            speedup=round(us_x / us_p, 3))
                if flops is not None:
                    case["mfu"] = round(flops / (us_p * 1e-6) / _peak(), 4)
                if bytes_moved is not None:
                    case["bw_frac"] = round(
                        bytes_moved / (us_p * 1e-6) / _peak_bw(), 4)
            if rspecs:
                memo = {}
                b = sum(modeled_launch_bytes(s, memo)["total_bytes"]
                        for s in rspecs)
                fl = [modeled_flops(s) for s in rspecs]
                f = sum(x for x in fl if x) if any(fl) else None
                rp = roofline_point(b, f, time_us=us_p)
                case.update(
                    bytes_modeled=int(b), flops_modeled=f,
                    intensity=rp["intensity"], bound=rp["bound"],
                    achieved_bw_frac=rp["achieved_bw_frac"],
                    achieved_flops_frac=rp["achieved_flops_frac"],
                    kernel_launches=sorted({s.name for s in rspecs}))
            res["cases"][name] = case
        except Exception as e:  # noqa: BLE001 — record, keep going
            import re
            msg = re.sub(r"\x1b\[[0-9;]*m", "", f"{type(e).__name__}: {e}")
            case = {"error": msg[:200]}
            if len(msg) > 200:
                # the Mosaic/XLA root cause is at the END, after the
                # HTTP/helper log noise
                case["error_tail"] = msg[-600:]
            res["cases"][name] = case

    # ---- flash attention (causal, GQA, varlen, bias) + backward --------
    B, S, H, KVH, D = (4, 2048, 16, 8, 128) if not interp \
        else (1, 256, 4, 2, 64)
    qk = jax.random.split(key, 8)
    q = jax.random.normal(qk[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(qk[1], (B, S, KVH, D), jnp.bfloat16)
    v = jax.random.normal(qk[2], (B, S, KVH, D), jnp.bfloat16)

    def ref_attn(q, k, v, causal=True, bias=None, seg=None):
        kr = jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)
        vr = jnp.repeat(v, q.shape[2] // v.shape[2], axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       kr.astype(jnp.float32)) / np.sqrt(q.shape[-1])
        if bias is not None:
            s = s + bias
        if causal:
            m = jnp.tril(jnp.ones((q.shape[1], kr.shape[1]), bool))
            s = jnp.where(m[None, None], s, -jnp.inf)
        if seg is not None:
            m = seg[:, None, :, None] == seg[:, None, None, :]
            s = jnp.where(m, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(jnp.isfinite(jnp.max(s, -1, keepdims=True)), p, 0.0)
        return jnp.einsum("bhqk,bkhd->bqhd", p,
                          vr.astype(jnp.float32)).astype(q.dtype)

    # causal fwd: QK^T + PV are 2*B*H*S*S*D each, halved by the mask
    fwd_flops = 2 * B * H * S * S * D
    record("flash_causal_gqa",
           jax.jit(lambda q, k, v: flash_attention_pallas(q, k, v,
                                                          causal=True)),
           jax.jit(lambda q, k, v: ref_attn(q, k, v, causal=True)),
           q, k, v, tol=3e-2, flops=fwd_flops)

    seg = jnp.concatenate([jnp.zeros((B, S // 2), jnp.int32),
                           jnp.ones((B, S - S // 2), jnp.int32)], axis=1)
    record("flash_varlen_seg",
           jax.jit(lambda q, k, v: flash_attention_pallas(
               q, k, v, causal=True, segment_ids=seg)),
           jax.jit(lambda q, k, v: ref_attn(q, k, v, causal=True, seg=seg)),
           q, k, v, tol=3e-2)

    # bias must be an ARGUMENT: a closure-captured [1,H,S,S] fp32 array
    # becomes an HLO constant baked into the program body
    bias = jax.random.normal(qk[3], (1, H, S, S), jnp.float32) * 0.1
    record("flash_bias",
           jax.jit(lambda q, k, v, b: flash_attention_pallas(
               q, k, v, causal=False, bias=b)),
           jax.jit(lambda q, k, v, b: ref_attn(q, k, v, causal=False,
                                               bias=b)),
           q, k, v, bias, tol=3e-2)

    del bias   # 268MB; keeping it live OOMs the ref-grad compile below

    def loss_p(q, k, v):
        return flash_attention_pallas(q, k, v, causal=True).astype(
            jnp.float32).sum()

    def loss_r(q, k, v):
        return ref_attn(q, k, v, causal=True).astype(jnp.float32).sum()

    # grad comparison on a half batch: the XLA reference backward holds
    # ~4GB of [B,H,S,S] fp32 temps and OOMs HBM at full B alongside the
    # other live case buffers (the Pallas kernel itself is fine at full B)
    qg, kg, vg = q[:B // 2], k[:B // 2], v[:B // 2]

    seed_dp = jnp.asarray(7, jnp.uint32)

    def ref_attn_dropout(q, k, v):
        from paddle_tpu.ops.flash_attention import _ref_attention
        return _ref_attention(q, k, v, causal=True, dropout_rate=0.2,
                              dropout_seed=seed_dp)

    record("flash_dropout",
           jax.jit(lambda q, k, v: flash_attention_pallas(
               q, k, v, causal=True, dropout_rate=0.2,
               dropout_seed=seed_dp)),
           jax.jit(ref_attn_dropout), q, k, v, tol=3e-2)

    # grad(loss) runs fwd + full bwd (dq,dk,dv): ~3.5x the fwd flops
    # (bwd is 2.5x: dP/dV matmuls + recomputed attention)
    bwd_flops = int(2 * (B // 2) * H * S * S * D * 3.5)
    record("flash_bwd_dq",
           jax.jit(lambda q, k, v: jax.grad(loss_p, 0)(q, k, v)),
           jax.jit(lambda q, k, v: jax.grad(loss_r, 0)(q, k, v)),
           qg, kg, vg, tol=6e-2, flops=bwd_flops)
    record("flash_bwd_dk",
           jax.jit(lambda q, k, v: jax.grad(loss_p, 1)(q, k, v)),
           jax.jit(lambda q, k, v: jax.grad(loss_r, 1)(q, k, v)),
           qg, kg, vg, tol=6e-2, flops=bwd_flops)

    # ---- paged-attention decode (incl. a seq_len=0 slot) ---------------
    PB, PH, PKV, PD, BS = (16, 16, 16, 128, 16) if not interp \
        else (4, 4, 4, 64, 8)
    NPAGES, MAXB = PB * 8, 8
    kp = jax.random.normal(qk[4], (NPAGES, BS, PKV, PD), jnp.bfloat16)
    vp = jax.random.normal(qk[5], (NPAGES, BS, PKV, PD), jnp.bfloat16)
    dq = jax.random.normal(qk[6], (PB, PH, PD), jnp.bfloat16)
    rng = np.random.RandomState(0)
    tables = jnp.asarray(
        rng.permutation(NPAGES)[:PB * MAXB].reshape(PB, MAXB), jnp.int32)
    lens = rng.randint(1, BS * MAXB, (PB,)).astype(np.int32)
    lens[0] = 0  # the untested-on-hardware edge from the verdict
    lens = jnp.asarray(lens)

    def ref_paged(dq, kp, vp):
        # Jittable mask-based composition (so the timed comparison is
        # Pallas kernel vs real XLA program, not Python dispatch): gather
        # every table page, mask positions >= seq_len.
        kk = kp[tables].reshape(PB, MAXB * BS, PKV, PD)
        vv = vp[tables].reshape(PB, MAXB * BS, PKV, PD)
        kk = jnp.repeat(kk, PH // PKV, 2).astype(jnp.float32)
        vv = jnp.repeat(vv, PH // PKV, 2).astype(jnp.float32)
        s = jnp.einsum("bhd,bkhd->bhk", dq.astype(jnp.float32),
                       kk) / np.sqrt(PD)
        live = jnp.arange(MAXB * BS)[None, :] < lens[:, None]
        s = jnp.where(live[:, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(lens[:, None, None] > 0, p, 0.0)  # len=0 -> zeros
        return jnp.einsum("bhk,bkhd->bhd", p, vv).astype(dq.dtype)

    # decode attention is pure HBM streaming — count only the LIVE pages
    # (the kernel reads ceil(len/BS) pages per sequence, not the whole
    # table; the full-table count would inflate bw_frac ~2x at these
    # random lens)
    live_pages = int(np.sum(np.ceil(np.asarray(lens) / BS)))
    paged_bytes = live_pages * BS * PKV * PD * 2 * 2  # bf16, k+v
    record("paged_decode",
           jax.jit(lambda dq, kp, vp: paged_attention_decode_pallas(
               dq, kp, vp, tables, lens)),
           jax.jit(ref_paged),
           dq, kp, vp, tol=3e-2, bytes_moved=paged_bytes)

    # ---- fused decode MLP block (serving hot path) ---------------------
    # the decode step's MLP launch vs the composition it replaces — the
    # same A/B the registry dispatches
    from paddle_tpu.ops.pallas.fused_decode_block import (
        fused_mlp_block_pallas, mlp_block_ref)

    FB, FD = (8, 1024) if not interp else (2, 64)
    FF = FD * 4                       # SwiGLU 4x
    fk = jax.random.split(jax.random.PRNGKey(1), 10)
    fx = jax.random.normal(fk[0], (FB, FD), jnp.bfloat16)
    fnw = jnp.ones((FD,), jnp.bfloat16)
    fwg = jax.random.normal(fk[7], (FD, FF), jnp.bfloat16) * 0.05
    fwu = jax.random.normal(fk[8], (FD, FF), jnp.bfloat16) * 0.05
    fwd_ = jax.random.normal(fk[9], (FF, FD), jnp.bfloat16) * 0.05
    record("fused_mlp_block",
           jax.jit(fused_mlp_block_pallas), jax.jit(mlp_block_ref),
           fx, fnw, fwg, fwu, fwd_, tol=5e-2,
           bytes_moved=3 * FD * FF * 2 + 2 * FB * FD * 2)

    # ---- quantized-WEIGHT variants (r18) -------------------------------
    # int8 / packed-int4 weight tiles with in-register dequant vs the
    # dequantize-then-matmul composition (both sides see the SAME
    # quantized tree, so the diff is kernel-vs-composition roundoff,
    # not quantization error) — same kernel_bench_gate trajectory
    from paddle_tpu.quantization import ptq as _ptq
    for wq_tag, wq_bits, wbytes in (("w8", 8, 1.0), ("w4", 4, 0.5)):
        qwg = _ptq.quantize_leaf(fwg, wq_bits)
        qwu = _ptq.quantize_leaf(fwu, wq_bits)
        qwd = _ptq.quantize_leaf(fwd_, wq_bits, pack_axis=1)
        record(f"fused_mlp_block_{wq_tag}",
               jax.jit(fused_mlp_block_pallas), jax.jit(mlp_block_ref),
               fx, fnw, qwg, qwu, qwd, tol=5e-2,
               bytes_moved=int(3 * FD * FF * wbytes) + 2 * FB * FD * 2)

    # ---- fused prefill-block megakernel (ragged chunked prefill) -------
    # one transformer block's prefill chunk (warm mid-window start,
    # ragged valid rows) vs the dense gather composition it replaces —
    # feeds the same kernel_bench_gate trajectory as the decode rows
    from paddle_tpu.ops.pallas.fused_prefill_block import (
        fused_prefill_attn_pallas, prefill_attn_block_ref)

    FKV, Fhd, FBS = (16, 64, 16) if not interp else (2, 16, 8)
    FH = FKV                          # MHA layout (groups=1)
    fwq = jax.random.normal(fk[1], (FD, FH * Fhd), jnp.bfloat16) * 0.05
    fwk = jax.random.normal(fk[2], (FD, FKV * Fhd), jnp.bfloat16) * 0.05
    fwv = jax.random.normal(fk[3], (FD, FKV * Fhd), jnp.bfloat16) * 0.05
    fwo = jax.random.normal(fk[4], (FH * Fhd, FD), jnp.bfloat16) * 0.05
    PP, PMB = (64, 24) if not interp else (16, 6)
    p_pos0, p_valid = (PMB * FBS) // 2, PP - 3
    pk = jax.random.split(jax.random.PRNGKey(4), 2)
    ppos = (p_pos0 + np.arange(PP))[:, None] / (
        10000.0 ** (np.arange(0, Fhd, 2) / Fhd))
    psin = jnp.asarray(np.sin(ppos), jnp.float32)
    pcos = jnp.asarray(np.cos(ppos), jnp.float32)
    px = jax.random.normal(pk[0], (PP, FD), jnp.bfloat16)
    PN = PMB + 2
    pkp = jax.random.normal(pk[1], (PN, FBS, FKV, Fhd), jnp.bfloat16)
    pvp = jax.random.normal(pk[0], (PN, FBS, FKV, Fhd), jnp.bfloat16)
    ptab = jnp.asarray(np.random.RandomState(5).permutation(PN - 1)
                       [:PMB] + 1, jnp.int32)
    # live traffic: block weights + the history pages + chunk I/O
    hist_pages = -(-p_pos0 // FBS)
    prefill_bytes = (2 * FD * FH * Fhd + 2 * FD * FKV * Fhd) * 2 \
        + hist_pages * FBS * FKV * Fhd * 2 * 2 + 2 * PP * FD * 2
    record("fused_prefill_attn",
           jax.jit(lambda *a: fused_prefill_attn_pallas(
               *a, jnp.int32(p_pos0), jnp.int32(p_valid))[0]
               [:p_valid]),
           jax.jit(lambda *a: prefill_attn_block_ref(
               *a, jnp.int32(p_pos0), jnp.int32(p_valid))[0]
               [:p_valid]),
           px, fnw, fwq, fwk, fwv, fwo, psin, pcos, pkp, pvp, ptab,
           tol=5e-2, bytes_moved=prefill_bytes)

    # ---- fused adamw ---------------------------------------------------
    N = 131072 * 32 if not interp else 4096
    p0 = jax.random.normal(qk[7], (N,), jnp.float32)
    g0 = jax.random.normal(qk[0], (N,), jnp.float32) * 0.01
    m0 = jnp.zeros((N,), jnp.float32)
    v0 = jnp.zeros((N,), jnp.float32)

    def ref_adamw(p, g, m, v):
        b1, b2, eps, wd, lr, step = 0.9, 0.999, 1e-8, 0.01, 1e-3, 1.0
        m2 = b1 * m + (1 - b1) * g
        v2 = b2 * v + (1 - b2) * g * g
        mh = m2 / (1 - b1 ** step)
        vh = v2 / (1 - b2 ** step)
        p2 = p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)
        return p2, m2, v2

    # reads p,g,m,v + writes p,m,v — 7 fp32 streams, pure bandwidth
    record("fused_adamw",
           jax.jit(lambda p, g, m, v: fused_adamw(p, g, m, v, 1e-3, 1.0)[0]),
           jax.jit(lambda p, g, m, v: ref_adamw(p, g, m, v)[0]),
           p0, g0, m0, v0, tol=1e-5, bytes_moved=N * 4 * 7)

    # ---- rms norm ------------------------------------------------------
    X = jax.random.normal(qk[1], (8192, 4096) if not interp else (64, 256),
                          jnp.bfloat16)
    W = jnp.ones((X.shape[-1],), jnp.bfloat16)

    def ref_rms(x, w):
        xf = x.astype(jnp.float32)
        return (xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
            * w.astype(jnp.float32)).astype(x.dtype)

    record("rms_norm", jax.jit(rms_norm_pallas), jax.jit(ref_rms),
           X, W, tol=3e-2, bytes_moved=X.size * 2 * 2)  # bf16 in+out

    LW = jax.random.normal(qk[2], (X.shape[-1],), jnp.bfloat16)
    LB = jax.random.normal(qk[3], (X.shape[-1],), jnp.bfloat16)

    def ref_ln(x, w, b):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
        return ((xf - mu) * jax.lax.rsqrt(var + 1e-5)
                * w.astype(jnp.float32)
                + b.astype(jnp.float32)).astype(x.dtype)

    # random weight/bias exercise the affine path; outputs of magnitude
    # ~4-8 differ from the reference by 1-2 bf16 ulps (f32 op order), so
    # the tolerance is 2 ulps at that magnitude
    record("layer_norm", jax.jit(layer_norm_pallas), jax.jit(ref_ln),
           X, LW, LB, tol=6.5e-2, bytes_moved=X.size * 2 * 2)

    # ---- fused residual-add + RMSNorm (decoder-block epilogue) ---------
    # both outputs (new residual stream y AND normed h) concatenated so
    # neither side can dead-code-eliminate half the kernel
    RD = jax.random.normal(qk[0], X.shape, jnp.bfloat16) * 0.1

    def _res_cat(fn):
        def run(d, x, w):
            y, h = fn(d, x, w)
            return jnp.concatenate([y.astype(jnp.float32).ravel(),
                                    h.astype(jnp.float32).ravel()])
        return run

    # reads delta+x, writes y+h — 4 bf16 row streams
    record("residual_rms_norm",
           jax.jit(_res_cat(residual_rms_norm_pallas)),
           jax.jit(_res_cat(residual_rms_norm_ref)),
           RD, X, W, tol=3e-2, bytes_moved=X.size * 2 * 4)

    # ---- fused training kernels (Liger-style hot path) -----------------
    # each case times the full fwd+bwd the trainer runs (grads
    # concatenated into ONE array so both variants must compute every
    # output — a tuple would defeat record()'s elementwise diff and let
    # XLA dead-code-eliminate half the backward). These feed the same
    # kernel_bench_gate as the decode kernels: once banked, a fusion
    # regression fails the bench run.
    from paddle_tpu.ops.pallas.fused_train import (linear_ce_pallas,
                                                   linear_ce_ref,
                                                   swiglu_pallas,
                                                   swiglu_ref)
    from paddle_tpu.ops.pallas.norms import (_rms_bwd_ref,
                                             rms_norm_bwd_pallas)

    CT, CD, CV = (4096, 2048, 32000) if not interp else (64, 64, 256)
    ck = jax.random.split(jax.random.PRNGKey(2), 6)
    ch = jax.random.normal(ck[0], (CT, CD), jnp.bfloat16) * 0.05
    chead = jax.random.normal(ck[1], (CD, CV), jnp.bfloat16) * 0.02
    clab = jnp.asarray(np.random.RandomState(1).randint(-1, CV, (CT,)),
                       jnp.int32)   # a few ignored labels in the mix

    def _ce_grads(fn):
        def run(x, h, l):
            loss, (dx, dh) = jax.value_and_grad(
                lambda a, b: fn(a, b, l), argnums=(0, 1))(x, h)
            return jnp.concatenate(
                [loss.reshape(1), dx.astype(jnp.float32).ravel(),
                 dh.astype(jnp.float32).ravel()])
        return run

    # fwd s + bwd recompute (x2) + dx + dh contractions: 5 matmuls of
    # 2·T·D·V each over the fused fwd+bwd
    record("fused_linear_ce", jax.jit(_ce_grads(linear_ce_pallas)),
           jax.jit(_ce_grads(linear_ce_ref)), ch, chead, clab,
           tol=3e-2, flops=10 * CT * CD * CV)

    SR, SF = (8192, 4096) if not interp else (64, 256)
    sg = jax.random.normal(ck[2], (SR, SF), jnp.bfloat16)
    su = jax.random.normal(ck[3], (SR, SF), jnp.bfloat16)

    def _swiglu_grads(fn):
        def run(g, u):
            dg, du = jax.grad(
                lambda a, b: fn(a, b).astype(jnp.float32).sum(),
                argnums=(0, 1))(g, u)
            return jnp.concatenate([dg.astype(jnp.float32).ravel(),
                                    du.astype(jnp.float32).ravel()])
        return run

    # fwd reads g+u, bwd reads g+u+d and writes dg+du — 7 bf16 streams
    record("fused_swiglu", jax.jit(_swiglu_grads(swiglu_pallas)),
           jax.jit(_swiglu_grads(swiglu_ref)), sg, su,
           tol=3e-2, bytes_moved=SR * SF * 2 * 7)

    # f32 case: the ref keeps dw in f32 (the composition's dtype), so a
    # bf16 comparison would only measure output rounding
    nx = jax.random.normal(ck[4], (SR, SF) if not interp else (64, 256),
                           jnp.float32)
    nw = jax.random.normal(jax.random.PRNGKey(5), (nx.shape[-1],),
                           jnp.float32)
    ng = jax.random.normal(ck[5], nx.shape, jnp.float32)

    def _rms_bwd_cat(dx, dw):
        return jnp.concatenate([dx.astype(jnp.float32).ravel(),
                                dw.astype(jnp.float32).ravel()])

    # reads x+g (+w), writes dx+dw — 4 f32 row streams dominate
    record("rms_norm_bwd",
           jax.jit(lambda x, w, g: _rms_bwd_cat(
               *rms_norm_bwd_pallas(x, w, g))),
           jax.jit(lambda x, w, g: _rms_bwd_cat(
               *_rms_bwd_ref(1e-6, (x, w), g))),
           nx, nw, ng, tol=2e-2, bytes_moved=nx.size * 4 * 4)

    # ---- roofline observatory report (BENCH_ROOFLINE=0 opts out) -------
    if roofline_on:
        try:
            res["roofline"] = _roofline_report()
        except Exception as e:  # noqa: BLE001 — the report must not
            res["roofline"] = {"error": str(e)[:200]}  # sink the bench

    n_ok = sum(1 for c in res["cases"].values() if c.get("ok"))
    res.update(metric="pallas_kernels_ok", value=n_ok,
               unit=f"of {len(res['cases'])} kernels", )
    return res


CONFIGS = {
    "resnet50": bench_resnet50,
    "resnet_breakdown": bench_resnet_breakdown,
    "llama": bench_llama,
    "llama_breakdown": bench_llama_breakdown,
    "ppyoloe": bench_ppyoloe,
    "flash_tune": bench_flash_tune,
    "bert": bench_bert,
    "ernie_infer": bench_ernie_infer,
    "paged_decode": bench_paged_decode,
    "serving_engine": bench_serving_engine,
    "serving_prefix_cache": bench_serving_prefix_cache,
    "serving_prefill": bench_serving_prefill,
    "serving_quant": bench_serving_quant,
    "serving_tp": bench_serving_tp,
    "serving_disagg": bench_serving_disagg,
    "serving_fleet": bench_serving_fleet,
    "sd_unet": bench_sd_unet,
    "kernels": bench_kernels,
}


def _run_child(name):
    """Entry for `bench.py --config NAME`: run one config, print its
    JSON. A config that raises ends the child with the traceback and a
    non-zero exit code, which the parent records as the failure."""
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    batch = int(os.environ.get("BENCH_BATCH", "256"))
    if name == "resnet50_one":
        # single operating point of the sweep ("batch:amp")
        point = os.environ.get("BENCH_RESNET_POINT", f"{batch}:O1")
        pb, _, pa = point.partition(":")
        r = bench_resnet50(steps=steps, batch=int(pb),
                           amp_level=pa or "O1")
    elif name == "resnet50":
        r = bench_resnet50(steps=steps, batch=batch)
    elif name == "llama_rung":
        # one LLAMA_LADDER rung per child (the parent sweeps them all)
        lsteps = int(os.environ.get("BENCH_LLAMA_STEPS", "6"))
        i = int(os.environ.get("BENCH_LADDER_IDX", "0"))
        label, lb, sq, h, L, it, acc, mdt = \
            LLAMA_LADDER[min(i, len(LLAMA_LADDER) - 1)]
        r = bench_llama(steps=lsteps, batch=lb, seq=sq, hidden=h,
                        layers=L, inter=it, accumulate=acc,
                        moment_dtype=mdt)
        r["label"] = label
    elif name == "llama":
        # One rung per CHILD process: a TPU OOM poisons the client, so
        # the sizing ladder lives in the parent (_spawn), which starts
        # a fresh process per rung. BENCH_LLAMA_RUNG selects the rung.
        lsteps = int(os.environ.get("BENCH_LLAMA_STEPS", "8"))
        rung = int(os.environ.get("BENCH_LLAMA_RUNG", "0"))
        lb, h, L, it, acc = LLAMA_RUNGS[min(rung, len(LLAMA_RUNGS) - 1)]
        if "BENCH_LLAMA_ACC" in os.environ:   # explicit operator override
            acc = int(os.environ["BENCH_LLAMA_ACC"])
        r = bench_llama(steps=lsteps, batch=lb, hidden=h, layers=L,
                        inter=it, accumulate=acc)
        r["rung"] = rung
    else:
        r = CONFIGS[name]()
    print(json.dumps(r))


# llama bench fallback ladder: (batch, hidden, layers, intermediate,
# accumulate_steps). Tried in order, each in a FRESH subprocess (TPU OOM
# poisons the client). Ordered by expected MFU: with the per-step h2d
# fix the step is device-bound, so more tokens per optimizer apply
# (batch x accumulation) amortize the per-param update; accumulation is
# kept moderate on the 740M rungs (the fp32 grad accumulator adds 3GB
# next to the 10.4GB optimizer state).
LLAMA_RUNGS = ((4, 2048, 12, 5504, 2), (2, 2048, 12, 5504, 2),
               (1, 2048, 12, 5504, 2), (8, 1536, 8, 4096, 2),
               (4, 1536, 8, 4096, 4), (2, 1024, 8, 2816, 4),
               (2, 1024, 8, 2816, 1))

# VERDICT r4 Next #2: the MFU-vs-params curve toward 7B-shaped dims
# (hidden 4096 x 32 heads is the LLaMA-2-7B layer geometry). Every rung
# runs in a FRESH subprocess and ALL rungs are attempted (curve, not
# fallback). Rungs past ~1B params switch the optimizer state to bf16
# moments (fp32 master kept): 2+4+2+2+2 = 12 bytes/param peak next to
# remat'd activations is what a 16GB v5e fits. Reference capability:
# sharding stage-3 trains 7B across chips
# (python/paddle/distributed/fleet/meta_parallel/sharding/
# group_sharded_stage3.py:85); single-chip rungs must prove the
# per-chip math before the multi-chip story means anything.
# (label, batch, seq, hidden, layers, inter, acc, moment_dtype)
LLAMA_LADDER = (
    ("325M", 8, 2048, 1536, 8, 4096, 2, None),
    ("740M", 4, 2048, 2048, 12, 5504, 2, None),
    ("1.10B", 4, 2048, 3072, 8, 8192, 1, "bfloat16"),
    ("1.07B-h4096", 2, 2048, 4096, 4, 11008, 1, "bfloat16"),
    ("1.27B-h4096", 1, 2048, 4096, 5, 11008, 1, "bfloat16"),
)

# resnet50 batch sweep (config "resnet50_sweep"): find the
# throughput-optimal batch on the chip, one FRESH subprocess per batch
# (an OOM at 512 must not poison the smaller runs).
# (batch, amp_level) operating points for the sweep. batch 256/O1 is the
# resnet50 config's default, already measured by the main PACK entry —
# the merge picks the best of sweep vs default. The O2 points run the
# whole net (incl. batch norm) in bf16 with fp32 master weights: the
# XPlane trace shows the step is BN/elementwise bandwidth-bound, and O1
# keeps BN in fp32, doubling exactly that traffic.
RESNET_SWEEP_POINTS = ("512:O1", "384:O1", "256:O2", "512:O2")


def _bank_partial(key, data):
    """Persist a ladder/sweep's per-rung progress (VERDICT.md Next #8):
    a parent killed mid-ladder (budget overrun) must still leave every
    completed rung on disk. One JSON file keyed by config,
    written atomically after each rung."""
    path = os.environ.get(
        "BENCH_BANK_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_LADDER_PARTIAL.json"))
    try:
        try:
            with open(path) as f:
                cur = json.load(f)
        except (OSError, json.JSONDecodeError):
            cur = {}
        cur[key] = data
        cur["t"] = round(time.time(), 1)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(cur, f)
        os.replace(tmp, path)
    except OSError:
        pass                     # banking must never kill the bench


def _env_ladder(name, var, values, timeout, per_cap, keep_best=False):
    """Run config `name` once per value of env var `var`, each in a
    FRESH subprocess (a TPU OOM poisons the client, so in-process
    ladders lose every later rung). keep_best=False returns the first
    success (fallback ladder); keep_best=True runs them all and returns
    the best "value" with a per-value "sweep" map. The caller's own
    `var` setting is saved and restored (the prober is a long-lived
    process; clobbering an operator override would leak across configs).
    """
    t0 = time.time()
    best, err, sweep = None, None, {}
    prev = os.environ.get(var)
    try:
        for v in values:
            left = timeout - (time.time() - t0)
            if left < 60:
                break
            os.environ[var] = str(v)
            r = _spawn(name, min(left, per_cap))
            if "error" not in r:
                if not keep_best:
                    _bank_partial(f"{name}:{var}",
                                  {"sweep": dict(sweep, **{str(v):
                                   r.get("value", 0)})})
                    return r
                sweep[str(v)] = r.get("value", 0)
                if best is None or r["value"] > best["value"]:
                    best = r
            else:
                err = r["error"]
                sweep[str(v)] = err[:80]
            _bank_partial(f"{name}:{var}", {"sweep": dict(sweep)})
    finally:
        if prev is None:
            os.environ.pop(var, None)
        else:
            os.environ[var] = prev
    if best is not None:
        best["sweep"] = sweep
        return best
    return {"error": err or f"timeout after {timeout}s", **(
        {"sweep": sweep} if keep_best else {})}


def _llama_ladder(timeout):
    """Run EVERY LLAMA_LADDER rung (fresh subprocess each) and report
    the MFU-vs-params curve; headline value = MFU at the largest rung
    that ran. Unlike the llama fallback ladder this is a sweep — an OOM
    at one rung is recorded in the curve and the next rung still runs."""
    t0 = time.time()
    curve, best = [], None
    prev = os.environ.get("BENCH_LADDER_IDX")
    try:
        for i, rung in enumerate(LLAMA_LADDER):
            left = timeout - (time.time() - t0)
            if left < 120:
                curve.append({"label": rung[0],
                              "error": "bench window exhausted"})
                continue
            os.environ["BENCH_LADDER_IDX"] = str(i)
            r = _spawn("llama_rung", min(left, 1200))
            r.setdefault("label", rung[0])
            keep = {k: r[k] for k in ("label", "value", "mfu", "params",
                                      "batch", "accumulate",
                                      "moment_dtype", "error")
                    if k in r}
            curve.append(keep)
            _bank_partial("llama_ladder",
                          {"curve": list(curve), "done": i + 1,
                           "total": len(LLAMA_LADDER)})
            if "error" not in r and (best is None
                                     or r["params"] > best["params"]):
                best = r
    finally:
        if prev is None:
            os.environ.pop("BENCH_LADDER_IDX", None)
        else:
            os.environ["BENCH_LADDER_IDX"] = prev
    if best is None:
        return {"error": "no ladder rung succeeded", "curve": curve}
    return {"metric": "llama_mfu_ladder", "value": best["mfu"],
            "unit": "MFU at largest rung", "top_rung": best["label"],
            "params": best["params"],
            "tokens_per_sec": best.get("value"), "curve": curve,
            "vs_baseline_mfu": round(best["mfu"] / 0.525, 4)}


def _spawn(name, timeout):
    """Run one config in a subprocess; return its parsed JSON or an error
    dict. Never raises, never hangs past `timeout`."""
    if name == "llama_ladder":
        return _llama_ladder(timeout)
    if name == "resnet50_sweep":
        return _env_ladder("resnet50_one", "BENCH_RESNET_POINT",
                           RESNET_SWEEP_POINTS, timeout, per_cap=600,
                           keep_best=True)
    if name == "llama" and "BENCH_LLAMA_RUNG" not in os.environ:
        return _env_ladder("llama", "BENCH_LLAMA_RUNG",
                           range(len(LLAMA_RUNGS)), timeout, per_cap=900)
    env = dict(os.environ)
    # sweep Pallas block configs on the chip; the winner persists in
    # ~/.cache/paddle_tpu/autotune.json, so the sweep cost is paid once
    # across all child configs (BENCH_AUTOTUNE=0 opts out)
    if os.environ.get("BENCH_AUTOTUNE", "1").lower() not in (
            "0", "false", "no"):
        env.setdefault("FLAGS_kernel_autotune", "1")
    try:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--config", name],
            capture_output=True, text=True, timeout=timeout, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        return {"error": f"timeout after {timeout}s"}
    if p.returncode != 0:
        return {"error": f"child rc={p.returncode}: "
                         f"{(p.stderr or '').strip()[-400:]}"}
    for line in reversed(p.stdout.strip().splitlines() or [""]):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return {"error": f"no JSON from child: {(p.stderr or '')[-200:]}"}


def main():
    """Run every config inside a global deadline budget, persisting
    partial results after each so a killed parent still leaves evidence
    on disk. Returns the exit code: non-zero when any config failed."""
    t_start = time.time()
    budget = float(os.environ.get("BENCH_BUDGET", "5400"))
    deadline = t_start + budget
    out = {"metric": "resnet50_train_imgs_per_sec_per_chip",
           "value": 0.0, "unit": "imgs/sec/chip", "vs_baseline": 0.0}
    failed = []
    partial = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_PARTIAL.json")

    def save_partial():
        try:
            with open(partial, "w") as f:
                json.dump(out, f)
        except OSError:
            pass

    def left():
        return deadline - time.time()

    def run_cfg(name, timeout):
        if left() < 90:
            r = {"error": "skipped (bench budget exhausted)"}
        else:
            r = _spawn(name, max(min(timeout, int(left())), 60))
        if "error" in r:
            failed.append(name)
            print(f"[bench] {name} failed: {r['error']}", file=sys.stderr)
        return r

    # -- config 1 ---------------------------------------------------------
    r = run_cfg("resnet50",
                int(os.environ.get("BENCH_RESNET_TIMEOUT", "1800")))
    if "error" in r:
        out["resnet_error"] = r["error"]
    else:
        out.update(r)
    save_partial()

    # -- config 3 (north star) ------------------------------------------
    r = run_cfg("llama", int(os.environ.get("BENCH_LLAMA_TIMEOUT", "1500")))
    if "error" in r:
        out["llama_error"] = r["error"]
    else:
        out["llama"] = r
    save_partial()

    # -- kernels validation + configs 2/4/6, on by default --------------
    if os.environ.get("BENCH_FAST", "0") in ("0", "", "false"):
        extra_t = int(os.environ.get("BENCH_EXTRA_TIMEOUT", "900"))
        for name in ("kernels", "ernie_infer", "paged_decode",
                     "serving_engine", "serving_prefix_cache",
                     "serving_prefill", "serving_quant", "serving_tp",
                     "serving_disagg", "sd_unet", "bert",
                     "resnet_breakdown", "ppyoloe", "llama_ladder"):
            if name == "kernels":
                _kernel_audit(out)   # pre-window geometry audit
            if name == "serving_engine":
                _lifecycle_audit(out)  # pre-serving state-machine gate
            out[name] = run_cfg(name, 2700 if name == "llama_ladder"
                                else extra_t)
            if name == "kernels":
                _kernel_gate(out)    # post-window regression diff
            save_partial()

    if failed:
        out["failed"] = failed
    save_partial()
    print(json.dumps(out))
    return 1 if failed else 0


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--config":
        _run_child(sys.argv[2])
    else:
        sys.exit(main())
