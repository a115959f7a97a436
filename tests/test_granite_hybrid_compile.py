"""The hybrid serving programs compiled at the benchmark configuration's
real shapes for a DESCRIBED v5e (no chip; as tests/test_chip_compile.py
does for the dense decoder): the state launches lower through Mosaic,
the state pools are updated in place, and the compiler holds no second
copy of them (handed a dynamic-slice and a dynamic-update-slice of one
slot's state, it re-laid the whole 2.4 GB pool out: 2.56 GB of
temporaries a chunk, PR 27)."""
import json
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import paddle_tpu  # noqa: F401
from paddle_tpu.inference import hybrid
from paddle_tpu.models import granite_hybrid as gh
from paddle_tpu.ops.pallas import _util

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {e}")


@pytest.fixture(autouse=True)
def _compile_for_the_chip(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    _util.set_force_interpret(False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    _util.set_force_interpret(None)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _shapes(topo, state_dtype):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite-4.0-h-small-l10-e36.json")) as f:
        conf = json.load(f)
    cfg = gh.GraniteHybridConfig(**{k: conf[k] for k in
                                    conf["program"]["config_keys"]})
    sh = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    def like(tree):
        return jax.tree_util.tree_map(lambda v: sds(v.shape, v.dtype), tree)

    eng = conf["engine"]
    C, BS = eng["capacity"], eng["block_size"]
    MB = -(-(eng["max_seq_len"] + max(eng["prefill_buckets"])) // BS)
    pool = sds((cfg.num_kv_layers, eng["num_blocks"], BS,
                cfg.num_key_value_heads, cfg.head_dim), cfg.dtype)
    params = like(jax.eval_shape(lambda: gh.init_params(cfg)))
    state = like(jax.eval_shape(
        lambda: hybrid.init_state(cfg, C, jnp.dtype(state_dtype))))
    return cfg, sds, params, pool, state, C, MB


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
def test_decode_program_updates_the_state_in_place(topo, state_dtype):
    cfg, sds, params, pool, state, C, MB = _shapes(topo, state_dtype)

    def program(params, tok, seq, tables, kp, vp, state):
        lg, kp, vp, state = hybrid.decode_step(params, tok, cfg, kp, vp,
                                               tables, seq, state)
        return jnp.argmax(lg, -1).astype(jnp.int32), kp, vp, state

    compiled = jax.jit(program, donate_argnums=(4, 5, 6)).lower(
        params, sds((C,), jnp.int32), sds((C,), jnp.int32),
        sds((C, MB), jnp.int32), pool, pool, state).compile()
    kernels = set(_util.compiled_kernel_counts(compiled.as_text()))
    assert {"ssm_update", "paged_attention_decode"} <= kernels
    assert "ragged-dot" in compiled.as_text()      # XLA's grouped product
    ssm_bytes = state["ssm"].size * state["ssm"].dtype.itemsize
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < ssm_bytes // 8
    assert mem.alias_size_in_bytes >= ssm_bytes     # donated, in place


def test_chunk_program_holds_no_copy_of_the_state_pool(topo):
    cfg, sds, params, pool, state, C, MB = _shapes(topo, "float32")
    P = 512

    def program(params, toks, pos0, table, wtable, last, kp, vp, slot,
                state):
        lg, kp, vp, state = hybrid.prefill_chunk(
            params, toks, cfg, kp, vp, table, wtable, pos0, last + 1,
            slot, state)
        return jnp.argmax(lg, -1), kp, vp, state

    compiled = jax.jit(program, donate_argnums=(6, 7, 9)).lower(
        params, sds((P,), jnp.int32), sds((), jnp.int32),
        sds((MB,), jnp.int32), sds((MB,), jnp.int32), sds((), jnp.int32),
        pool, pool, sds((), jnp.int32), state).compile()
    kernels = set(_util.compiled_kernel_counts(compiled.as_text()))
    assert {"ssm_state_read", "ssm_state_write"} <= kernels
    ssm_bytes = state["ssm"].size * state["ssm"].dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < ssm_bytes // 4
