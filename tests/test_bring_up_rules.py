"""The rules that keep the program honest about where it runs (PR 21):
one compile-cache directory that can be placed from outside, no kernel
fallback that hides the device, no Mosaic kernel where GSPMD partitions
the program, and a ``chip_smoke.py`` that refuses a machine without a
chip and fails when a phase fails.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from paddle_tpu.core import backend
from paddle_tpu.ops.pallas import _util
from paddle_tpu.ops.pallas.registry import KERNELS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, **env):
    """A fresh interpreter from the repo root, on the CPU."""
    args = ([sys.executable, "-c", code_or_args]
            if isinstance(code_or_args, str)
            else [sys.executable] + list(code_or_args))
    e = {k: v for k, v in os.environ.items()
         if k != "JAX_COMPILATION_CACHE_DIR"}
    e.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run(args, cwd=REPO, env=e, capture_output=True,
                          text=True, timeout=600)


# -- the compile cache ----------------------------------------------------
_SHOW_CACHE = ("import jax, paddle_tpu; "
               "from paddle_tpu.ops.pallas import autotune; "
               "print(jax.config.jax_compilation_cache_dir); "
               "print(autotune._CACHE_PATH)")


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout():
    """Unset, every process lands on <checkout>/.jax_cache — the path is
    part of the cache key, so two processes must agree on it — and the
    autotune table sits beside the compiled programs."""
    outs = [_run(_SHOW_CACHE).stdout.split() for _ in range(2)]
    assert outs[0] == outs[1] == [
        os.path.join(REPO, ".jax_cache"),
        os.path.join(REPO, ".jax_cache", "autotune.json")]


def test_cache_dir_from_the_environment_is_never_overridden(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it, the code sets none
    (and the autotune table follows it there)."""
    where = str(tmp_path / "placed")
    out = _run(_SHOW_CACHE, JAX_COMPILATION_CACHE_DIR=where).stdout.split()
    assert out == [where, os.path.join(where, "autotune.json")]


def test_configure_sets_nothing_when_the_variable_is_set(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    backend.configure_compile_cache()
    assert calls == [] and backend.cache_dir() == "/somewhere/else"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    backend.configure_compile_cache()
    assert calls == [("jax_compilation_cache_dir",
                      os.path.join(REPO, ".jax_cache"))]


# -- no fallback that hides the device ------------------------------------
def _boom(*a, **kw):
    raise RuntimeError("Mosaic failed to compile TPU kernel")


def _call_flash():
    from paddle_tpu.ops.flash_attention import flash_attention
    q = jnp.ones((1, 128, 2, 64), jnp.float32)
    return flash_attention(q, q, q, causal=True)


def _call_paged():
    from paddle_tpu.ops.paged_attention import paged_attention_decode
    # head_dim 128: at 16 the registry itself keeps the launch off a
    # TPU (whole 128-lane rows), and nothing would be there to fail
    pool = jnp.ones((4, 8, 2, 128), jnp.float32)
    return paged_attention_decode(
        jnp.ones((2, 2, 128), jnp.float32), pool, pool,
        jnp.zeros((2, 2), jnp.int32), jnp.ones((2,), jnp.int32))


def _call_rms():
    from paddle_tpu.ops import rms_norm
    return rms_norm(jnp.ones((4, 128), jnp.float32),
                    jnp.ones((128,), jnp.float32))


@pytest.mark.parametrize("module,kernel,call", [
    ("paddle_tpu.ops.pallas.flash_attention", "flash_attention_pallas",
     _call_flash),
    ("paddle_tpu.ops.pallas.paged_attention",
     "paged_attention_decode_pallas", _call_paged),
    ("paddle_tpu.ops.pallas.norms", "rms_norm_pallas", _call_rms),
], ids=["flash_attention", "paged_attention_decode", "rms_norm"])
def test_kernel_failure_on_a_tpu_backend_raises(monkeypatch, module,
                                                kernel, call):
    """Off-TPU the router takes the composition; on a TPU backend a
    kernel that fails is an error, never a quiet composition."""
    import importlib
    assert np.all(np.isfinite(np.asarray(call())))      # CPU: composition
    monkeypatch.setattr(importlib.import_module(module), kernel, _boom)
    assert np.all(np.isfinite(np.asarray(call())))      # still not called
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        call()


def test_flash_router_sends_untileable_lengths_to_the_composition(
        monkeypatch):
    """Chosen by a shape predicate, not an except: 600 rows are past
    one 512-row block and not a multiple of it (the kernel's tail block
    would read past the array), so even on a TPU backend the kernel is
    not called."""
    from paddle_tpu.ops import flash_attention as fa
    from paddle_tpu.ops.pallas import flash_attention as pfa
    monkeypatch.setattr(pfa, "flash_attention_pallas", _boom)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jnp.ones((1, 600, 2, 64), jnp.float32)
    out = fa.flash_attention(q, q, q)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(fa._ref_attention(q, q, q)))


def test_one_predicate_answers_is_this_a_tpu(monkeypatch):
    from paddle_tpu import device
    assert not backend.on_tpu() and _util.interpret_mode()
    assert not device.is_compiled_with_tpu()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert backend.on_tpu() and not _util.interpret_mode()
    assert device.is_compiled_with_tpu() and _util.pallas_route()


# -- GSPMD cannot partition a Mosaic kernel ---------------------------------
def test_gspmd_scope_refuses_every_pallas_variant_with_the_reason(
        monkeypatch):
    """Inside the trace of a program GSPMD partitions over > 1 device,
    dispatch takes the composition and says why; one device, a
    shard_map body (no scope) and the interpreter are unaffected."""
    from paddle_tpu.ops.pallas import fused_train as ft
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    meta = ft.swiglu_meta(4096, 11008, "bfloat16")
    assert KERNELS.dispatch("fused_swiglu", meta)[0] == "pallas_fused"
    with _util.gspmd_program(1):
        assert _util.gspmd_refusal() is None and _util.pallas_route()
    with _util.gspmd_program(4):
        assert not _util.pallas_route()
        assert KERNELS.dispatch("fused_swiglu", meta)[0] == "unfused"
        (row,) = [r for r in KERNELS.explain("fused_swiglu", meta)
                  if r["name"] == "pallas_fused"]
        assert "cannot be automatically partitioned" in row["reason"]
        # an explicit pin is the caller's word, as everywhere
        with KERNELS.force("fused_swiglu", "pallas_fused"):
            assert KERNELS.dispatch("fused_swiglu", meta)[0] == \
                "pallas_fused"
    assert _util.gspmd_refusal() is None           # scope restored
    monkeypatch.undo()
    with _util.gspmd_program(4):                   # interpreter: no Mosaic
        assert _util.gspmd_refusal() is None


def test_trainer_traces_its_step_inside_the_scope_of_its_mesh():
    from paddle_tpu.distributed.trainer import (MeshConfig, Trainer,
                                                make_mesh)
    seen = []

    def loss(p, x):
        seen.append(getattr(_util._GSPMD, "n", 1))
        return jnp.sum((x @ p["w"]) ** 2)

    from jax.sharding import PartitionSpec as P
    for mc, want in ((MeshConfig(), 1), (MeshConfig(fsdp=2, tp=2), 4)):
        mesh = make_mesh(mc)
        tr = Trainer(loss, mesh, {"w": P()}, data_spec=P())
        state = tr.init_state({"w": jnp.ones((8, 8), jnp.float32)})
        tr.step(state, jnp.ones((4, 8), jnp.float32))
        assert seen[-1] == want
    assert getattr(_util._GSPMD, "n", 1) == 1


# -- chip_smoke.py ----------------------------------------------------------
def test_chip_smoke_refuses_a_machine_without_a_tpu():
    """No TPU, no --tiny: non-zero before any model work, no result."""
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0 and "needs a TPU" in r.stderr
    assert r.stdout == ""


def test_chip_smoke_needs_the_program_beside_it(tmp_path):
    """In a directory that holds the script and nothing else of the
    repo it fails, even at the rehearsal size."""
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = subprocess.run(
        [sys.executable, "chip_smoke.py", "--tiny"], cwd=tmp_path,
        env=dict({k: v for k, v in os.environ.items()
                  if k != "PYTHONPATH"}, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and '"ok"' not in r.stdout


def test_chip_smoke_rehearsal_ends_ok_and_names_the_cpu():
    r = _run(["chip_smoke.py", "--tiny"])
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    count = last["device"]["count"]        # conftest's virtual devices
    assert count >= 1 and last == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": count}}
    phases = {json.loads(ln)["phase"]: json.loads(ln) for ln in lines[:-1]}
    assert set(phases) == {"start", "sync", "serving", "pattern",
                           "training"}
    s, t, p = phases["serving"], phases["training"], phases["pattern"]
    # the window + global + expert model: a prompt past the window
    assert p["first_token_equal"] and p["window_pages_released"] > 0
    assert max(p["prompt_lens"]) > p["window"]["positions"]
    assert s["finished"] == s["requests"] == 6
    assert s["first_token_equal"] and s["retrace_warnings"] == 0
    assert max(s["prompt_lens"]) > max(s["prefill_buckets"])
    assert s["prefix_cache"]["hits"] >= 1
    assert t["steps"] >= 3 and t["losses"][-1] < t["losses"][0]


def test_chip_smoke_exits_nonzero_when_a_phase_fails():
    """A phase whose check fails raises: the exit code is non-zero,
    what came before it is printed, no later phase runs and the ok line
    is not printed (nothing catches an error and carries on)."""
    r = _run("import sys, chip_smoke\n"
             "def sync(*a): chip_smoke.check(False, 'made to fail')\n"
             "chip_smoke.phase_sync = sync\n"
             "sys.argv = ['chip_smoke.py', '--tiny']; chip_smoke.main()")
    assert r.returncode != 0
    assert "chip_smoke: made to fail" in r.stderr
    assert '"phase": "start"' in r.stdout
    assert '"ok"' not in r.stdout and '"serving"' not in r.stdout
