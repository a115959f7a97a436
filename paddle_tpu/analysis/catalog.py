"""Canonical program catalog for the audit gate.

``tools/program_audit.py`` and the tier-1 ``pytest -m audit`` test need
one shared, deterministic set of "the programs this framework ships":
the hybrid-parallel trainer step, the fused eager-optimizer step, the
serving engine's decode + per-bucket prefill programs, the prefix-cache
COW page copier, and a shard_map collectives program. The builders here
construct each one at a TINY, CPU-traceable size — audits only trace,
so tiny shapes exercise the identical program structure the production
sizes compile — and register the specs through the same component hooks
production code uses (``Trainer.audit_spec``,
``ServingEngine.program_specs``, ``Optimizer.audit_spec``), keeping the
catalog honest: it cannot drift from what the components actually run.

``build_catalog`` returns the specs; it does not audit. The deliberate
REGRESSION specimen (the pre-fix AdamW update, kept as a tracing
fixture for the dtype rule's self-test and the CLI's
``--demo-regression`` gate check) is opt-in and never part of the
default catalog.
"""
from __future__ import annotations

from typing import List, Optional

__all__ = ["build_catalog", "build_demo_regression",
           "build_demo_tp_regression", "CATALOG_PROGRAMS"]

# the default gate set, in audit order
CATALOG_PROGRAMS = ("train_step", "train_step_fused",
                    "fused_optimizer_step",
                    "serving_decode", "serving_decode_fused",
                    "serving_decode_wq",
                    "serving_prefill_16", "serving_prefill_32",
                    "serving_prefill_fused",
                    "serving_page_copy",
                    "serving_kv_spill_extract",
                    "serving_kv_restore_insert",
                    "serving_decode_tp", "serving_prefill_tp_16",
                    "disagg_decode", "disagg_prefill_16",
                    "disagg_kv_extract", "disagg_kv_insert",
                    "collectives")


def _tiny_llama_cfg(seq: int = 64):
    from ..models.llama import LlamaConfig
    return LlamaConfig(vocab_size=128, hidden_size=64,
                       intermediate_size=128, num_hidden_layers=2,
                       num_attention_heads=2, num_key_value_heads=2,
                       max_position_embeddings=seq, remat=False)


def _trainer_spec(register: bool):
    import jax
    import numpy as np
    from ..distributed.trainer import MeshConfig, Trainer, make_mesh
    from ..models.llama import init_params, loss_fn, param_shardings

    cfg = _tiny_llama_cfg(seq=32)
    mesh = make_mesh(MeshConfig())
    params = init_params(cfg, jax.random.PRNGKey(0))
    tr = Trainer(lambda p, t, l: loss_fn(p, t, l, cfg), mesh,
                 param_shardings(mesh, cfg), lr=1e-4)
    state = tr.init_state(params)
    toks = np.zeros((2, 32), np.int32)
    return tr.audit_spec(state, toks, np.zeros((2, 32), np.int32),
                         register=register)


def _trainer_fused_spec(register: bool):
    """The SAME tiny trainer step with the fused training path pinned
    to the Pallas kernels (``cfg.fused_train="pallas"``), so the
    audited jaxpr contains the fused linear+CE custom_vjp, SwiGLU and
    RMSNorm-backward/residual-epilogue kernels even on CPU (where
    auto-dispatch falls back to the composition) — the gate must cover
    the program production TPUs actually run. Built with
    register=False and re-registered under its own name: audit_spec's
    "train_step" would otherwise latest-wins clobber the default
    trainer's entry in the global REGISTRY (the serving_decode_fused
    idiom). The fp32 loss accumulation feeds the dtype-promotion rule;
    the donated state tree feeds the donation rule."""
    import dataclasses as _dc

    import jax
    import numpy as np
    from ..distributed.trainer import MeshConfig, Trainer, make_mesh
    from ..models.llama import init_params, loss_fn, param_shardings

    cfg = _dc.replace(_tiny_llama_cfg(seq=32), fused_train="pallas")
    mesh = make_mesh(MeshConfig())
    params = init_params(cfg, jax.random.PRNGKey(0))
    tr = Trainer(lambda p, t, l: loss_fn(p, t, l, cfg), mesh,
                 param_shardings(mesh, cfg), lr=1e-4)
    state = tr.init_state(params)
    toks = np.zeros((2, 32), np.int32)
    spec = tr.audit_spec(state, toks, np.zeros((2, 32), np.int32),
                         register=False)
    spec = _dc.replace(spec, name="train_step_fused",
                       tags=spec.tags + ("fused",))
    if register:
        from .registry import REGISTRY
        REGISTRY.register(spec)
    return spec


def _fused_optimizer_spec(register: bool):
    import numpy as np
    import paddle_tpu as paddle
    from ..optimizer import AdamW

    w = paddle.to_tensor(np.zeros((64, 64), np.float32),
                         stop_gradient=False)
    b = paddle.to_tensor(np.zeros((64,), np.float32),
                         stop_gradient=False)
    loss = (w.sum() + b.sum())
    loss.backward()
    opt = AdamW(learning_rate=1e-3, parameters=[w, b], weight_decay=0.01)
    opt.step()          # builds + records the fused update program
    return opt.audit_spec(register=register)


def _pinned(spec, name: str, tags=()):
    """``spec`` renamed, its program traced with both decode launches
    pinned to their Pallas variants (the registry's ``force``)."""
    import dataclasses as _dc
    from ..ops.pallas.registry import KERNELS

    def fn(*args):
        with KERNELS.force("paged_attention_decode", "pallas"), \
                KERNELS.force("decode_mlp_block", "pallas_fused"):
            return spec.fn(*args)
    return _dc.replace(spec, name=name, fn=fn, tags=spec.tags + tags)


def _serving_specs(register: bool):
    import jax
    from ..inference.serving import ServingEngine
    from ..models.llama import init_params

    cfg = _tiny_llama_cfg(seq=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, capacity=2, block_size=8,
                        max_seq_len=64, prefill_buckets=(16, 32),
                        prefix_cache=True)
    specs = eng.program_specs(register=register)
    # the decode program with BOTH launches forced onto their Pallas
    # variants, so the audited jaxpr contains the kernels even on CPU
    # (auto-dispatch picks the compositions there) — the gate must
    # cover the program production TPUs actually run. Registered
    # renamed, next to (never latest-wins clobbering) the default
    # program's entry
    # (a FRESH jit instance: one the unpinned audit has traced would
    # replay that trace under the pins)
    import dataclasses as _dc
    fused = [_pinned(s, "serving_decode_fused")
             for s in eng.program_specs(register=False)
             if s.name == "serving_decode"]
    # the fused PREFILL chunk the same way: a forced-pallas-prefill
    # engine's bucket program, renamed to its catalog entry (the
    # audited jaxpr contains the prefill megakernels even on CPU)
    fp_eng = ServingEngine(params, cfg, capacity=2, block_size=8,
                           max_seq_len=64, prefill_buckets=(16,),
                           fused_prefill="pallas")
    fused += [_dc.replace(s, name="serving_prefill_fused")
              for s in fp_eng.program_specs(register=False)
              if s.name == "serving_prefill_fused_16"]
    # the quantized-WEIGHT decode program: an int8 weight tree's
    # decode step pinned the same way, so the quantized param signature
    # (integer leaves + scale leaves), the MLP launch's in-kernel
    # dequantization and the attention stage's dequantize-then-matmul
    # route feed the dtype/donation/retrace rules
    wq_eng = ServingEngine(params, cfg, capacity=2, block_size=8,
                           max_seq_len=64, prefill_buckets=(16,),
                           weight_quant="int8")
    fused += [_pinned(s, "serving_decode_wq", tags=("weight_quant",))
              for s in wq_eng.program_specs(register=False)
              if s.name == "serving_decode"]
    if register:
        from .registry import REGISTRY
        for s in fused:
            REGISTRY.register(s)
    return specs + fused


def _serving_offload_specs(register: bool):
    """The host-RAM KV offload tier's handoff pair (the spill-side
    single-page extract and the donated restore-side insert) from a
    prefix-cached engine with ``kv_offload`` on. Registered filtered,
    like the fused-decode spec: the offload engine's other programs
    would latest-wins clobber the main engine's entries while the gate
    list kept auditing the main engine's versions."""
    import jax
    from ..inference.serving import ServingEngine
    from ..models.llama import init_params

    cfg = _tiny_llama_cfg(seq=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, capacity=2, block_size=8,
                        max_seq_len=64, prefill_buckets=(16,),
                        prefix_cache=True, kv_offload=True)
    specs = [s for s in eng.program_specs(register=False)
             if s.name in ("serving_kv_spill_extract",
                           "serving_kv_restore_insert")]
    if register:
        from .registry import REGISTRY
        for s in specs:
            REGISTRY.register(s)
    return specs


def _tp_cfg():
    """Divisible head counts for the tensor-parallel serving specs
    (the default tiny cfg's KV=2 only shards 2-way)."""
    from ..models.llama import LlamaConfig
    import jax.numpy as jnp
    return LlamaConfig(vocab_size=128, hidden_size=64,
                       intermediate_size=128, num_hidden_layers=2,
                       num_attention_heads=4, num_key_value_heads=4,
                       max_position_embeddings=64, dtype=jnp.float32,
                       remat=False)


def _catalog_tp() -> int:
    """Largest supported tp degree on the visible devices (CI forces 8
    virtual CPU devices -> 4; a bare single-device env still builds the
    same program NAMES at tp=1, so the gate list never shrinks)."""
    import jax
    n = len(jax.devices())
    return max(t for t in (1, 2, 4) if t <= n)


def _serving_tp_specs(register: bool):
    """The REAL tensor-parallel serving programs: a mesh'd engine's
    decode + prefill, registered with their declared mesh axes so the
    collective-consistency rule gates actual sharded programs — the
    psums/all_gathers live inside the shard_map'd jaxpr, and the
    declared ``mesh_axes`` must agree with the mesh the programs were
    built over."""
    import jax
    from ..inference.serving import ServingEngine
    from ..inference.tp import ServingMesh
    from ..models.llama import init_params

    cfg = _tp_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(params, cfg, capacity=2, block_size=8,
                        max_seq_len=64, prefill_buckets=(16,),
                        mesh=ServingMesh.make(tp=_catalog_tp()))
    specs = [s for s in eng.program_specs(register=False)
             if s.name in ("serving_decode_tp", "serving_prefill_tp_16")]
    if register:
        from .registry import REGISTRY
        for s in specs:
            REGISTRY.register(s)
    return specs


def _serving_disagg_specs(register: bool):
    """The disaggregated engine's programs: the decode group's decode
    step, the prefill group's bucketed prefill, and the KV-page
    handoff pair (extract on the prefill pools, donated insert into
    the decode pools). Built over 1-device groups — two devices where
    the environment has them, the single-device overlap fallback
    otherwise — so the gate list never shrinks (the ``_catalog_tp``
    idiom)."""
    import jax
    from ..inference.disagg import DisaggregatedEngine
    from ..models.llama import init_params

    cfg = _tp_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    devs = jax.devices()
    eng = DisaggregatedEngine(
        params, cfg, prefill_devices=devs[:1],
        decode_devices=devs[1:2] or devs[:1],
        capacity=2, prefill_slots=1, block_size=8, max_seq_len=64,
        prefill_buckets=(16,))
    specs = [s for s in eng.program_specs(register=False)
             if s.name in ("disagg_decode", "disagg_prefill_16",
                           "disagg_kv_extract", "disagg_kv_insert")]
    if register:
        from .registry import REGISTRY
        for s in specs:
            REGISTRY.register(s)
    return specs


def _collectives_spec(register: bool):
    """A representative multichip program: shard_map over the full
    device set with the collective families the flight recorder's op
    taxonomy tracks (psum / all_gather / ppermute)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from ..core.jax_compat import shard_map
    from .registry import ProgramSpec, REGISTRY

    devs = np.array(jax.devices())
    mesh = Mesh(devs.reshape(len(devs), 1), ("dp", "tp"))

    n = len(devs)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(x):
        y = jax.lax.psum(x, "dp")
        g = jax.lax.all_gather(y, "tp")
        z = jax.lax.ppermute(g.sum(0), "dp", perm)
        return jax.lax.psum(z, "tp")

    fn = jax.jit(shard_map(body, mesh=mesh,
                           in_specs=P("dp", None), out_specs=P(),
                           check_vma=False))
    spec = ProgramSpec(
        name="collectives", fn=fn,
        args=(jax.ShapeDtypeStruct((2 * len(devs), 8), jnp.float32),),
        mesh_axes=("dp", "tp"), tags=("distributed",))
    if register:
        REGISTRY.register(spec)
    return spec


def build_catalog(names: Optional[List[str]] = None,
                  register: bool = True):
    """Build the canonical ProgramSpecs (all of CATALOG_PROGRAMS, or
    the requested subset). Building is trace-free — specs hold only
    callables + abstract signatures."""
    wanted = set(names) if names is not None else set(CATALOG_PROGRAMS)
    unknown = wanted - set(CATALOG_PROGRAMS)
    if unknown:
        # a typo'd (or since-renamed) program name must never let a CI
        # gate pass vacuously after auditing nothing
        raise ValueError(
            f"unknown catalog program(s): {sorted(unknown)} — known: "
            f"{list(CATALOG_PROGRAMS)}")
    specs = []
    if "train_step" in wanted:
        specs.append(_trainer_spec(register))
    if "train_step_fused" in wanted:
        specs.append(_trainer_fused_spec(register))
    if "fused_optimizer_step" in wanted:
        specs.append(_fused_optimizer_spec(register))
    if wanted & {"serving_decode", "serving_decode_fused",
                 "serving_decode_wq",
                 "serving_prefill_16", "serving_prefill_32",
                 "serving_prefill_fused", "serving_page_copy"}:
        specs.extend(s for s in _serving_specs(register)
                     if s.name in wanted)
    if wanted & {"serving_kv_spill_extract",
                 "serving_kv_restore_insert"}:
        specs.extend(s for s in _serving_offload_specs(register)
                     if s.name in wanted)
    if wanted & {"serving_decode_tp", "serving_prefill_tp_16"}:
        specs.extend(s for s in _serving_tp_specs(register)
                     if s.name in wanted)
    if wanted & {"disagg_decode", "disagg_prefill_16",
                 "disagg_kv_extract", "disagg_kv_insert"}:
        specs.extend(s for s in _serving_disagg_specs(register)
                     if s.name in wanted)
    if "collectives" in wanted:
        specs.append(_collectives_spec(register))
    return specs


def build_demo_regression(register: bool = False):
    """The PRE-FIX AdamW update as an auditable spec: ``1 - b1**step``
    with an int32 step drops its weak type under the global x64 flag
    and widens the fp32 master tree to float64 — the bug PR-4's compile
    telemetry caught at runtime and this auditor catches statically.
    Used by the rule self-test and the CLI's ``--demo-regression``
    injected-regression check; never in the default catalog."""
    import jax
    import jax.numpy as jnp
    from .registry import ProgramSpec, REGISTRY

    def prefix_adamw(master, mu, nu, step, lr, g):
        b1, b2, eps = 0.9, 0.95, 1e-8
        step = step + 1
        mu_n = b1 * mu + (1 - b1) * g
        nu_n = b2 * nu + (1 - b2) * jnp.square(g)
        mhat = mu_n / (1 - b1 ** step)          # the bug: f64 under x64
        vhat = nu_n / (1 - b2 ** step)
        m_n = master - lr * mhat / (jnp.sqrt(vhat) + eps)
        return m_n, mu_n, nu_n, step

    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    spec = ProgramSpec(
        name="demo_regression_adamw",
        fn=jax.jit(prefix_adamw, donate_argnums=(0, 1, 2, 3)),
        args=(f32((256,)), f32((256,)), f32((256,)),
              jax.ShapeDtypeStruct((), jnp.int32), f32(()), f32((256,))),
        donate_argnums=(0, 1, 2, 3),
        carry={0: 0, 1: 1, 2: 2, 3: 3}, tags=("demo",))
    if register:
        REGISTRY.register(spec)
    return spec


def build_demo_tp_regression(register: bool = False):
    """Mismatched mesh-axis injection for the collective rule: the REAL
    per-shard tensor-parallel decode body (``inference.tp
    ._tp_decode_step``, psum placement) traced under its true axis
    binding (``axis_env=(("tp", 2),)`` — the body hardcodes psum over
    "tp") while the spec DECLARES ``mesh_axes=("model",)``. That is
    exactly the bug a mesh-axis rename introduces: the engine would
    provide an axis named "model", the body still reduces over "tp",
    and the program cannot run on the declared mesh.
    ``UNKNOWN_COLLECTIVE_AXIS`` must fire — the CLI's
    ``--demo-regression`` gate self-check covers the sharded serving
    path with it. Never part of the default catalog."""
    import functools

    import jax
    import jax.numpy as jnp
    from ..inference.tp import _tp_decode_step
    from .registry import ProgramSpec, REGISTRY

    cfg, tp = _tp_cfg(), 2
    L, D = cfg.num_hidden_layers, cfg.hidden_size
    H, KV, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    F, V = cfg.intermediate_size, cfg.vocab_size
    B, BS, NB, MB = 2, 8, 9, 8
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    isd = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)    # noqa: E731
    # the LOCAL shard's parameter shapes (what shard_map hands the body)
    params_sd = {
        "embed_tokens": sds(V, D), "final_norm": sds(D),
        "lm_head": sds(D, V),
        "layers": {
            "input_norm": sds(L, D), "post_norm": sds(L, D),
            "q_proj": sds(L, D, H * hd // tp),
            "k_proj": sds(L, D, KV * hd // tp),
            "v_proj": sds(L, D, KV * hd // tp),
            "o_proj": sds(L, H * hd // tp, D),
            "gate_proj": sds(L, D, F // tp),
            "up_proj": sds(L, D, F // tp),
            "down_proj": sds(L, F // tp, D),
        },
    }
    pools_sd = sds(L, NB, BS, KV // tp, hd)
    fn = functools.partial(_tp_decode_step, cfg=cfg, axis="tp",
                           collective="psum")
    spec = ProgramSpec(
        name="demo_regression_tp_axis",
        fn=lambda params, tok, kp, vp, tables, seq: fn(
            params, tok, k_pools=kp, v_pools=vp, block_tables=tables,
            seq_lens=seq),
        args=(params_sd, isd(B), pools_sd, pools_sd, isd(B, MB),
              isd(B)),
        mesh_axes=("model",),          # the mismatch: body psums @tp
        axis_env=(("tp", tp),), tags=("demo",))
    if register:
        REGISTRY.register(spec)
    return spec
