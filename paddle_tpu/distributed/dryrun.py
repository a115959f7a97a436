"""Multichip dryrun: compile + run ONE full LLaMA training step over an
n-device mesh with real dp/fsdp/tp/sp shardings (driver contract
``__graft_entry__.dryrun_multichip``).

The mesh is built from the default backend's devices, and a backend
with fewer than ``n`` raises. The caller chooses the virtual CPU mesh,
from outside: ``JAX_PLATFORMS=cpu`` with
``XLA_FLAGS=--xla_force_host_platform_device_count=<n>`` — the same way
``tests/conftest.py`` does (the reference tests multi-rank on one host
the same way, SURVEY.md §4).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models.llama import (LlamaConfig, init_params, loss_fn,
                            param_shardings)
from .trainer import MeshConfig, Trainer, make_mesh


def resolve_devices(n: int):
    """The first ``n`` devices of the default backend; raises when it
    has fewer (a mesh that was asked for is never quietly swapped for
    another platform's)."""
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"{n} devices asked for, the {devices[0].platform} backend "
            f"has {len(devices)}. For a virtual CPU mesh run with "
            f"JAX_PLATFORMS=cpu XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n}")
    return devices[:n]


def _factor(n: int):
    """Split n devices into (dp, fsdp, tp, sp) covering all axes >1 when
    possible."""
    if n == 1:
        return MeshConfig()
    if n % 8 == 0:
        return MeshConfig(dp=n // 8, fsdp=2, tp=2, sp=2)
    if n % 4 == 0:
        return MeshConfig(dp=n // 4, fsdp=2, tp=2, sp=1)
    if n % 2 == 0:
        return MeshConfig(dp=n // 2, fsdp=2)
    return MeshConfig(dp=n)


def run_dryrun(n_devices: int) -> None:
    _run_dryrun(n_devices)
    if n_devices >= 4 and n_devices % 2 == 0:
        # the gate also exercises the pipeline axis (compiled 1F1B) and
        # the dp allreduce path
        _run_dryrun_pp(n_devices)
        # expert parallelism: the remaining first-class axis family
        # (SURVEY §2.4 MoE) — ep-sharded experts, GSPMD dispatch
        _run_dryrun_ep(n_devices)
        # sep-axis ring/ulysses attention forward+backward parity
        # against the single-device reference
        _run_dryrun_sep(n_devices)
        # distributed-checkpoint reshard — save on mesh(n), resume
        # exactly on mesh(n/2)
        _run_dryrun_ckpt(n_devices)
        # tensor-parallel sharded serving — a tp-sharded ServingEngine
        # over the mesh with greedy bit-parity vs the single-device one
        _run_dryrun_serving_tp(n_devices)


def _run_dryrun(n_devices: int) -> None:
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=64,
                      dtype=jnp.float32, remat=True)
    mc = _factor(n_devices)
    devices = resolve_devices(n_devices)
    mesh = make_mesh(mc, devices=devices)
    params = init_params(cfg, jax.random.key(0))
    specs = param_shardings(mesh, cfg)

    def loss(params, tokens, labels):
        return loss_fn(params, tokens, labels, cfg)

    trainer = Trainer(loss, mesh, specs,
                      data_spec=P(("dp", "fsdp"), "sp"), lr=1e-3,
                      observability=True)
    state = trainer.init_state(params)
    B = max(mc.dp * mc.fsdp, 1) * 2
    S = max(mc.sp, 1) * 16
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)),
                         dtype=jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)),
                         dtype=jnp.int32)
    state, metrics = trainer.step(state, tokens, labels)
    jax.block_until_ready(metrics["loss"])
    loss0 = float(metrics["loss"])
    assert np.isfinite(loss0), f"non-finite loss {loss0}"
    # the observed step must have telemetered its compile: wall time,
    # cost-analysis flops (MFU numerator) and the per-step phase split
    tm = trainer.metrics()
    assert tm["compiles"] >= 1, tm
    assert tm["latency"]["step_ms"]["count"] == 1, tm
    comp = tm["compile"]["programs"]["train_step"]
    from ..ops.pallas._util import interpret_mode
    print(f"dryrun_multichip ok: n={n_devices} mesh="
          f"{dict(mesh.shape)} platform={devices[0].platform} "
          f"pallas_interpret={interpret_mode()} loss={loss0:.4f} "
          f"grad_norm={float(metrics['grad_norm']):.4f} "
          f"compile_ms={comp['wall_ms_last']:.0f} "
          f"flops/step={(comp.get('cost') or {}).get('flops', 0):.3g} "
          f"hbm_total={((comp.get('memory') or {}).get('total_bytes', 0))}")


def _run_dryrun_pp(n_devices: int) -> None:
    """Second gate phase: a pp2 x dp(n/2) mesh driving the compiled 1F1B
    schedule (ppermute activation/cotangent shifts, per-microbatch vjp
    remat, in-graph dp grad allreduce) plus one SGD update."""
    from jax.sharding import Mesh
    from .fleet.pp_compiled import Compiled1F1B

    S, DP, M, mb, D = 2, n_devices // 2, 8, 2 * (n_devices // 2), 16
    devices = resolve_devices(n_devices)
    mesh = Mesh(np.array(devices[:n_devices]).reshape(S, DP), ("pp", "dp"))
    rng = np.random.RandomState(0)
    W = jnp.asarray(rng.randn(S, 2, D, D) * 0.1, jnp.float32)
    B = jnp.asarray(rng.randn(S, 2, D) * 0.1, jnp.float32)

    def stage_fn(p, x):
        w, b = p
        for i in range(2):
            x = jnp.tanh(x @ w[i] + b[i])
        return x

    def loss_fn(y, label):
        return jnp.mean((y - label) ** 2)

    pipe = Compiled1F1B(stage_fn, loss_fn, mesh, num_microbatches=M,
                        split_dw=True, data_axis="dp")
    x = jnp.asarray(rng.randn(M, mb, D), jnp.float32)
    y = jnp.asarray(rng.randn(M, mb, D), jnp.float32)

    @jax.jit
    def train_step(params, x, y):
        loss, grads = pipe.loss_and_grads(params, x, y)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree_util.tree_leaves(grads)))
        params = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g,
                                        params, grads)
        return params, loss, gnorm

    with mesh:
        (W, B), loss, gnorm = train_step((W, B), x, y)
        jax.block_until_ready(loss)
    loss0, gn0 = float(loss), float(gnorm)
    assert np.isfinite(loss0), f"non-finite pp loss {loss0}"
    assert np.isfinite(gn0), f"non-finite pp grad_norm {gn0}"
    print(f"dryrun_multichip ok: n={n_devices} mesh="
          f"{dict(mesh.shape)} schedule=compiled_1f1b_zb(dp_allreduce) "
          f"loss={loss0:.4f} grad_norm={gn0:.4f}")


def _run_dryrun_ep(n_devices: int) -> None:
    """Third gate phase: expert parallelism. An ep x dp mesh with the
    expert-stacked MLP weights sharded over ``ep`` and tokens over
    ``dp``; the MoE dispatch/combine einsums become GSPMD cross-expert
    collectives (the reference's global_scatter/global_gather pair,
    SURVEY §2.4). One fwd+bwd+SGD step, loss/grad-norm must be finite."""
    from jax.sharding import Mesh, NamedSharding
    from .fleet.moe import moe_dispatch_combine

    EP, DP = 2, n_devices // 2
    devices = resolve_devices(n_devices)
    mesh = Mesh(np.array(devices[:n_devices]).reshape(EP, DP),
                ("ep", "dp"))
    T, D, H, E = 8 * DP, 16, 32, 2 * EP
    rng = np.random.RandomState(0)
    shard = lambda a, *spec: jax.device_put(
        jnp.asarray(a, jnp.float32), NamedSharding(mesh, P(*spec)))
    gate_w = shard(rng.randn(D, E) * 0.1)
    w_in = shard(rng.randn(E, D, H) * 0.1, "ep")
    w_out = shard(rng.randn(E, H, D) * 0.1, "ep")
    x = shard(rng.randn(T, D), "dp")
    tgt = shard(rng.randn(T, D), "dp")

    def loss_of(params, x, tgt):
        gw, wi, wo = params

        def expert_fn(expert_in):            # [E, C, D] -> [E, C, D]
            h = jnp.tanh(jnp.einsum("ecd,edh->ech", expert_in, wi))
            return jnp.einsum("ech,ehd->ecd", h, wo)

        out, aux = moe_dispatch_combine(x, x @ gw, expert_fn, top_k=2)
        return jnp.mean((out - tgt) ** 2) + 0.01 * aux

    @jax.jit
    def train_step(params, x, tgt):
        loss, grads = jax.value_and_grad(loss_of)(params, x, tgt)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree_util.tree_leaves(grads)))
        params = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g,
                                        params, grads)
        return params, loss, gnorm

    with mesh:
        compiled = train_step.lower((gate_w, w_in, w_out), x, tgt) \
            .compile()
        txt = compiled.as_text()
        params, loss, gnorm = compiled((gate_w, w_in, w_out), x, tgt)
        jax.block_until_ready(loss)
    loss0, gn0 = float(loss), float(gnorm)
    assert np.isfinite(loss0), f"non-finite ep loss {loss0}"
    assert np.isfinite(gn0), f"non-finite ep grad_norm {gn0}"
    colls = [c for c in ("all-to-all", "all-gather", "all-reduce",
                         "reduce-scatter", "collective-permute")
             if c in txt]
    assert colls, "ep program compiled without any cross-device collective"
    print(f"dryrun_multichip ok: n={n_devices} mesh="
          f"{dict(mesh.shape)} moe=ep-sharded experts "
          f"collectives={','.join(colls)} loss={loss0:.4f} "
          f"grad_norm={gn0:.4f}")


def _run_dryrun_sep(n_devices: int) -> None:
    """Fourth gate phase: long-context sequence parallelism over the
    ``sep`` axis (reference: distributed/topology.py:199 sep groups;
    ring attention exceeds the reference, SURVEY §5). Both ring
    attention (ppermute KV rotation) and ulysses attention (all_to_all
    head redistribution) run forward AND backward over an n-way
    seq-sharded mesh and must match the single-device reference."""
    from jax.sharding import Mesh
    from ..ops.flash_attention import _ref_attention
    from ..ops.ring_attention import ring_attention, ulysses_attention

    devices = resolve_devices(n_devices)
    mesh = Mesh(np.array(devices[:n_devices]), ("sep",))
    b, s, h, d = 2, n_devices * 8, n_devices, 16
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, s, h, d) * 0.3, jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d) * 0.3, jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d) * 0.3, jnp.float32)

    ref = _ref_attention(q, k, v, causal=True)
    gref = jax.grad(lambda q: jnp.sum(
        _ref_attention(q, k, v, causal=True) ** 2))(q)

    with mesh:
        for name, fn in (("ring", ring_attention),
                         ("ulysses", ulysses_attention)):
            out = jax.jit(lambda q, k, v, f=fn: f(
                q, k, v, mesh, axis_name="sep", causal=True))(q, k, v)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-4,
                err_msg=f"{name} attention forward diverges")
            g = jax.jit(jax.grad(lambda q, f=fn: jnp.sum(f(
                q, k, v, mesh, axis_name="sep", causal=True) ** 2)))(q)
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(gref), atol=2e-3,
                err_msg=f"{name} attention backward diverges")
    print(f"dryrun_multichip ok: n={n_devices} mesh={{'sep': "
          f"{n_devices}}} ring+ulysses fwd/bwd parity vs single-device "
          f"(s={s})")


def _run_dryrun_ckpt(n_devices: int) -> None:
    """Fifth gate phase: distributed checkpoint with reshard-on-load
    (reference: checkpoint/load_state_dict.py:526). Train 2 steps on an
    n-device fsdp mesh, save, reload into an (n/2)-device mesh, take one
    more step on each — the resumed loss must match the uninterrupted
    run exactly (same global arrays, same math)."""
    import tempfile

    from jax.sharding import Mesh, NamedSharding
    from ..core.tensor import Tensor
    from .checkpoint.save_load import load_state_dict, save_state_dict

    devices = resolve_devices(n_devices)
    half = n_devices // 2
    rng = np.random.RandomState(0)
    w0 = rng.randn(2 * n_devices, 16).astype(np.float32) * 0.2
    x = jnp.asarray(rng.randn(8, 2 * n_devices), jnp.float32)
    y = jnp.asarray(rng.randn(8, 16), jnp.float32)

    def step(w, x, y):
        loss, g = jax.value_and_grad(
            lambda w: jnp.mean((x @ w - y) ** 2))(w)
        return w - 0.1 * g, loss

    mesh_a = Mesh(np.array(devices[:n_devices]), ("fsdp",))
    sh_a = NamedSharding(mesh_a, P("fsdp"))
    w = jax.device_put(jnp.asarray(w0), sh_a)
    with mesh_a:
        step_a = jax.jit(step)
        for _i in range(2):
            w, _loss = step_a(w, x, y)
        with tempfile.TemporaryDirectory() as ckpt:
            save_state_dict({"w": Tensor(w)}, ckpt)
            _, loss_uninterrupted = step_a(w, x, y)

            mesh_b = Mesh(np.array(devices[:half]), ("fsdp",))
            sh_b = NamedSharding(mesh_b, P("fsdp"))
            wb = Tensor(jax.device_put(jnp.zeros_like(jnp.asarray(w0)),
                                       sh_b))
            load_state_dict({"w": wb}, ckpt)
        with mesh_b:
            _, loss_resumed = jax.jit(step)(wb._value, x, y)
    lu, lr_ = float(loss_uninterrupted), float(loss_resumed)
    assert np.isfinite(lr_), f"non-finite resumed loss {lr_}"
    np.testing.assert_allclose(
        lr_, lu, rtol=1e-6,
        err_msg="resume after save(mesh n)->load(mesh n/2) diverged")
    print(f"dryrun_multichip ok: n={n_devices} ckpt reshard "
          f"fsdp{n_devices}->fsdp{half} exact resume loss={lr_:.6f}")


def _run_dryrun_serving_tp(n_devices: int) -> None:
    """Sixth gate phase: tensor-parallel sharded serving (ROADMAP #1
    stage 1). A ServingEngine over a tp mesh (inference/tp.py — KV
    pools, projections and per-slot attention sharded along the head
    axis via shard_map) serves a mixed stream with greedy BIT-parity
    vs the single-device engine (collective="gather", the documented
    bit-identical placement), exactly one decode program and <=1 trace
    per prefill bucket, and the declared per-step collectives counted
    by the bound flight recorder."""
    from ..inference import GenerationConfig, ServingEngine, ServingMesh
    from ..models.llama import init_params

    devices = resolve_devices(n_devices)
    tp = 4 if n_devices >= 4 else 2
    cfg = LlamaConfig(vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=4,
                      max_position_embeddings=64, dtype=jnp.float32,
                      remat=False)
    params = init_params(cfg, jax.random.key(0), dtype=jnp.float32)

    def run(mesh, obs):
        rng = np.random.RandomState(0)   # same prompts both runs
        eng = ServingEngine(params, cfg, capacity=2, block_size=8,
                            max_seq_len=64, prefill_buckets=(16,),
                            mesh=mesh, observability=obs)
        rs = [eng.submit(rng.randint(0, 128, (int(s),))
                         .astype(np.int32),
                         GenerationConfig(max_new_tokens=8,
                                          greedy=True))
              for s in [7, 12, 5, 9, 11, 6]]
        eng.drain()
        return eng, [r.output_ids for r in rs]

    _, ref = run(None, False)
    mesh = ServingMesh.make(tp=tp, collective="gather",
                            devices=devices[:tp])
    eng, out = run(mesh, True)
    assert all(np.array_equal(a, b) for a, b in zip(ref, out)), \
        "tp-sharded greedy output diverged from the single-device engine"
    m = eng.metrics()
    assert m["decode_traces"] == 1, m["decode_traces"]
    assert all(v <= 1 for v in m["prefill_traces"].values()), \
        m["prefill_traces"]
    calls = m.get("collectives", {}).get("calls", {})
    print(f"dryrun_multichip ok: n={n_devices} mesh={{'tp': {tp}}} "
          f"serving_tp collective=gather parity=bit decode_programs=1 "
          f"prefill_traces={dict(m['prefill_traces'])} "
          f"collective_calls={dict(calls)}")
