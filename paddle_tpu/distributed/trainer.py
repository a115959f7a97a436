"""Sharded functional trainer — the Fleet-equivalent hot path.

Builds ONE pjit-compiled train step for a functional model (params pytree +
loss fn) over a named mesh with the full hybrid-parallel layout:
- dp: batch data parallel (outermost, DCN-friendly)
- fsdp: ZeRO-3 parameter/grad/state sharding (reference group_sharded
  stage-3 semantics, group_sharded_stage3.py:85 — here GSPMD inserts the
  gather-on-use / reduce-scatter-on-grad and XLA overlaps them)
- tp: Megatron tensor parallel (reference mp_layers.py)
- sp: sequence/context parallel on the activation seq dim (reference sep
  axis, topology.py:77)

The optimizer is a functional AdamW with fp32 master weights + moments,
all sharded like their params (stage-1/2 are the same code with params
replicated). This is the train loop the reference builds out of
HybridParallelOptimizer + DygraphShardingOptimizer + EagerReducer + manual
comm groups — here it is ~200 lines because the compiler owns comm.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.backend import on_tpu
from ..observability import (CompileWatcher, HostGapDetector,
                             Observability, TRAIN_HISTOGRAMS,
                             TelemetryConfig, TelemetryPlane,
                             live_hbm_bytes, programs as _programs, span,
                             tracing)

__all__ = ["MeshConfig", "make_mesh", "TrainState", "Trainer"]


def _fused_train_key():
    """Everything that can flip the fused-training-kernel dispatch at
    TRACE time: the FLAGS_fused_train mode, any registry force pins,
    the scoped-VMEM budget (it reshapes the supports() predicates and
    the tile-candidate lists) and the interpret override. A loss_fn
    routed through the registry (models/llama.py, models/gpt.py) bakes
    the dispatched variant into the traced step, so a changed key must
    REBUILD the step program — not silently replay a program traced
    under the old routing (the same contract generation.py's
    _PAGED_CACHE route key keeps for the decode megakernels)."""
    from ..ops.pallas._util import (fused_train_mode, fused_vmem_budget,
                                    interpret_mode)
    from ..ops.pallas.registry import KERNELS
    return (fused_train_mode(), KERNELS.forced_state(),
            fused_vmem_budget(), bool(interpret_mode()))


@dataclasses.dataclass
class MeshConfig:
    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1

    @property
    def total(self):
        return self.dp * self.fsdp * self.tp * self.sp * self.pp


def make_mesh(cfg: MeshConfig, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    if cfg.total > len(devices):
        raise ValueError(f"need {cfg.total} devices, have {len(devices)}")
    arr = np.array(devices[:cfg.total]).reshape(
        cfg.pp, cfg.dp, cfg.fsdp, cfg.sp, cfg.tp)
    return Mesh(arr, axis_names=("pp", "dp", "fsdp", "sp", "tp"))


class TrainState:
    """params (model dtype) + fp32 master/moments, all mesh-sharded."""

    def __init__(self, params, master, mu, nu, step):
        self.params = params
        self.master = master
        self.mu = mu
        self.nu = nu
        self.step = step

    def tree(self):
        return (self.params, self.master, self.mu, self.nu, self.step)

    @staticmethod
    def from_tree(t):
        return TrainState(*t)


def _adamw_update(grads, state: Tuple, lr, b1=0.9, b2=0.95, eps=1e-8,
                  wd=0.1, grad_clip=1.0):
    params, master, mu, nu, step = state
    step = step + 1
    with jax.named_scope("optimizer/clip"):
        gnorm_sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                       for g in jax.tree_util.tree_leaves(grads))
        gnorm = jnp.sqrt(gnorm_sq)
        scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gnorm, 1e-12)) \
            if grad_clip else 1.0
    # bias corrections pinned to float32: `b1 ** step` with an int32
    # step promotes through float64 under the global x64 flag (the
    # Python float drops its weak type against the integer array),
    # which widened the whole master tree after step 1 and recompiled
    # step 2 in every earlier bench window. pow(f32, f32) is the same
    # computation the weak-typed path ran in f32 mode — bit-identical.
    with jax.named_scope("optimizer/update"):
        stepf = step.astype(jnp.float32)
        bc1 = 1.0 - jnp.float32(b1) ** stepf
        bc2 = 1.0 - jnp.float32(b2) ** stepf

    def upd(g, m, mu_i, nu_i):
        g32 = g.astype(jnp.float32) * scale
        mu_n = b1 * mu_i.astype(jnp.float32) + (1 - b1) * g32
        nu_n = b2 * nu_i.astype(jnp.float32) + (1 - b2) * jnp.square(g32)
        mhat = mu_n / bc1
        vhat = nu_n / bc2
        m_n = m * (1.0 - lr * wd) - lr * mhat / (jnp.sqrt(vhat) + eps)
        # moments keep their stored dtype (bf16 under a reduced
        # moment_dtype policy) so state shapes/dtypes are step-invariant
        return m_n, mu_n.astype(mu_i.dtype), nu_n.astype(nu_i.dtype)

    flat_g = jax.tree_util.tree_leaves(grads)
    flat_m = jax.tree_util.tree_leaves(master)
    flat_mu = jax.tree_util.tree_leaves(mu)
    flat_nu = jax.tree_util.tree_leaves(nu)
    treedef = jax.tree_util.tree_structure(grads)
    new_m, new_mu, new_nu = [], [], []
    with jax.named_scope("optimizer/update"):
        for g, m, mi, ni in zip(flat_g, flat_m, flat_mu, flat_nu):
            a, b, c = upd(g, m, mi, ni)
            new_m.append(a)
            new_mu.append(b)
            new_nu.append(c)
    master_n = jax.tree_util.tree_unflatten(treedef, new_m)
    mu_n = jax.tree_util.tree_unflatten(treedef, new_mu)
    nu_n = jax.tree_util.tree_unflatten(treedef, new_nu)
    with jax.named_scope("optimizer/params_out"):
        params_n = jax.tree_util.tree_map(
            lambda m, p: m.astype(p.dtype), master_n, params)
    return (params_n, master_n, mu_n, nu_n, step), gnorm


def _sharding_cache_key(v):
    """Hashable EQUIVALENCE key for a leaf's sharding. NamedSharding
    __eq__ is syntactic — on any mesh, ``P()`` vs ``P(None,)`` vs a
    spec naming only SIZE-1 axes all place the array identically, and
    XLA output shardings routinely flip between those spellings. Keyed
    raw they would recompile a semantically identical program (a
    1-device mesh would pay a spurious step-2 compile); so the key
    drops size-1 mesh axes from the spec and trailing replicated dims,
    keeping only partitions that move bytes."""
    sh = getattr(v, "sharding", None)
    mk = getattr(sh, "memory_kind", None)
    if not isinstance(sh, NamedSharding):
        if sh is not None and len(sh.device_set) == 1:
            # a fresh uncommitted array (SingleDeviceSharding) and a
            # replicated NamedSharding over a 1-device mesh place the
            # bytes identically — same key, no spurious recompile
            return ("single", frozenset(sh.device_set), mk)
        return sh
    mesh_shape = sh.mesh.shape
    spec = []
    for entry in sh.spec:
        names = (() if entry is None
                 else entry if isinstance(entry, tuple) else (entry,))
        names = tuple(n for n in names if mesh_shape[n] > 1)
        spec.append(names or None)
    while spec and spec[-1] is None:
        spec.pop()
    if not spec and len(sh.device_set) == 1:
        return ("single", frozenset(sh.device_set), mk)
    return ("named", tuple(sorted(mesh_shape.items())), tuple(spec), mk)


class Trainer:
    def __init__(self, loss_fn: Callable, mesh: Mesh,
                 param_specs, data_spec=P(("dp", "fsdp"), "sp"),
                 lr=3e-4, b1=0.9, b2=0.95, weight_decay=0.1,
                 grad_clip=1.0, accumulate_steps: int = 1,
                 donate: bool = True,
                 fused_optimizer: Optional[bool] = None,
                 moment_dtype=None,
                 observability=False,
                 host_gap_factor: float = 4.0,
                 host_gap_min_ms: float = 50.0,
                 telemetry=False):
        """loss_fn(params, *batch) -> scalar. param_specs: pytree of
        PartitionSpec matching params.

        fused_optimizer: None = auto. On a single-device mesh the AdamW
        update runs as ONE Pallas multi-tensor pass over flat fp32
        master/moment state with the low-precision shadow written in
        the same pass (reference fused_adam_kernel.cu semantics). XLA's
        per-leaf update measured ~50ms on a 325M model where the HBM
        bound is ~11ms. On multi-device meshes the per-leaf path keeps
        every state tensor sharded like its param, so it stays the
        default. Mixed floating param trees (bf16 weights + fp32 norms,
        the llama layout) are supported: fp32 leaves are sliced back
        from the fp32 master, shadow-dtype leaves from the shadow.

        moment_dtype: storage dtype for the AdamW mu/nu state (None =
        fp32). bfloat16 halves optimizer-state HBM (10 -> 6 bytes per
        param next to the fp32 master), the policy that lets the
        single-chip ladder climb past ~1B params on 16GB; the update
        math still runs in fp32 (reference multi_precision AdamW,
        python/paddle/optimizer/adamw.py _multi_precision path).

        observability: True (or an ``Observability`` instance) threads
        the metrics/tracing harness through ``step()``/``prefetch()``:
        per-step phase histograms (stage/h2d, compiled dispatch, host
        sync), loss/grad-norm/prefetch-queue-depth/live-HBM gauges,
        compile telemetry (wall time, retrace counts, cost-analysis
        FLOPs for automatic MFU, memory-analysis HBM breakdown) and a
        host-vs-device gap detector that emits a flight-recorder-style
        dump when host-side time dwarfs the device wait (the llama-
        bench h2d-residual failure mode). The observed step runs the
        SAME jitted program through the AOT ``lower().compile()`` path
        (identical HLO, bit-identical numerics) and adds ONE per-step
        metrics sync; disabled, the hot path is byte-for-byte the old
        one — no event objects, no extra device syncs.
        """
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.param_specs = param_specs
        self.data_spec = data_spec
        self.lr = lr
        self.hp = dict(b1=b1, b2=b2, wd=weight_decay, grad_clip=grad_clip)
        self.accumulate_steps = accumulate_steps
        self._step_fn = None
        # the registry of compiled programs (observability/programs.py)
        # captures the step at its first dispatch UNDER A PROFILER SESSION
        self._program_keys = []
        self._noted = False
        self._donate = donate
        self._fused_opt = fused_optimizer
        self._fused = False
        self._flat_meta = None
        # what the step's optimizer compiled in; None until it traces
        self._optimizer_variant = None
        self.moment_dtype = moment_dtype
        # throughput counters exist in both modes (cheap dict ticks —
        # the frozen metrics schema needs them); the harness itself is
        # None when disabled, so the disabled loop allocates no event
        # objects and issues no extra device syncs
        self.counters = {"steps": 0, "samples": 0, "tokens": 0}
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None
        # telemetry implies observability (alerts land timeline events
        # and stall dumps, both owned by the harness)
        _tcfg = TelemetryConfig.coerce(telemetry)
        if observability or _tcfg is not None:
            self._obs = (observability
                         if isinstance(observability, Observability)
                         else Observability(histograms=TRAIN_HISTOGRAMS))
            self._obs.registry.adopt_counters(self.counters)
            self._compile = CompileWatcher(self._obs.registry,
                                           self._obs.timeline)
            self._gap = HostGapDetector(factor=host_gap_factor,
                                        min_wall_ms=host_gap_min_ms)
            self._compiled_cache: Dict = {}
            self._aot_fallback = False
        else:
            self._obs = None
            self._compile = None
            self._gap = None
            self._compiled_cache = None
        # continuous telemetry plane (r22): samples metrics() on a
        # step cadence; None when disabled
        self._telemetry = None
        if _tcfg is not None:
            self._telemetry = TelemetryPlane(
                _tcfg, on_alert=self._telemetry_alert)
            self._telemetry.register("trainer", self.metrics,
                                     counters=self.counters)

    # -- state init ----------------------------------------------------------
    @staticmethod
    def _fused_tree_ok(params) -> bool:
        """Param-tree eligibility for the flat fused path: non-empty,
        all-floating, and at most ONE dtype besides fp32 — fp32 leaves
        slice back from the fp32 master, the rest from the single
        low-precision shadow (llama's bf16-weights + fp32-norms layout).
        Shared by auto-decide and the forced-path validation so the two
        can never drift."""
        leaves = jax.tree_util.tree_leaves(params)
        non_f32 = {v.dtype for v in leaves} - {jnp.dtype(jnp.float32)}
        return (len(leaves) > 0
                and all(jnp.issubdtype(v.dtype, jnp.floating)
                        for v in leaves)
                and len(non_f32) <= 1)

    @staticmethod
    def _flat_layout(params):
        """``_flat_meta`` of a parameter tree (arrays or shapes): how
        the fused path lays its flat master / moment state out."""
        from ..ops.pallas.fused_adamw import BLOCK
        leaves = jax.tree_util.tree_leaves(params)
        sizes = [int(np.prod(v.shape)) for v in leaves]
        # pad the flat state to a kernel-block multiple: whole tiles of
        # the launch's 2-D view at every dtype, so the view is a bitcast
        # and the launch pads nothing. Padding tail sees zero grads, so
        # its moments stay zero.
        pad = (-sum(sizes)) % BLOCK
        # one low-precision shadow dtype; fp32 leaves slice back
        # from the master itself (exact) so an all-fp32 tree needs
        # no shadow output at all
        non_f32 = [v.dtype for v in leaves
                   if v.dtype != jnp.dtype(jnp.float32)]
        return (jax.tree_util.tree_structure(params),
                [v.shape for v in leaves], sizes,
                non_f32[0] if non_f32 else None, pad,
                [v.dtype for v in leaves])

    def _decide_fused(self, params) -> bool:
        if self._fused_opt is not None:
            return bool(self._fused_opt)
        if self.mesh.devices.size != 1:
            return False   # per-leaf path keeps state sharded like params
        if not on_tpu():
            return False   # interpret-mode pallas would be slower than XLA
        return self._fused_tree_ok(params)

    def init_state(self, params) -> TrainState:
        shard = lambda tree: jax.tree_util.tree_map(
            lambda v, s: jax.device_put(v, NamedSharding(self.mesh, s)),
            tree, self.param_specs)
        params = shard(params)
        self._fused = self._decide_fused(params)
        if self._fused and self._fused_opt:
            # forced fused path must still satisfy _decide_fused's
            # preconditions: flat unsharded state on a multi-device mesh
            # silently drops FSDP sharding (and likely OOMs), and a
            # mixed-dtype tree would cast every leaf to leaves[0].dtype
            if self.mesh.devices.size != 1:
                raise ValueError(
                    "fused_optimizer=True builds flat UNSHARDED "
                    "master/moment state — unsupported on a "
                    f"{self.mesh.devices.size}-device mesh (param "
                    "sharding would be lost). Use fused_optimizer=None "
                    "(auto) or False.")
            if not self._fused_tree_ok(params):
                dts = sorted({str(v.dtype) for v in
                              jax.tree_util.tree_leaves(params)})
                raise ValueError(
                    "fused_optimizer=True requires a non-empty param "
                    "tree of floating dtype with at most one dtype "
                    f"besides float32 (one flat shadow); got {dts}.")
        step = jnp.zeros((), jnp.int32)
        mdt = self.moment_dtype or jnp.float32
        if self._fused:
            leaves = jax.tree_util.tree_leaves(params)
            self._flat_meta = self._flat_layout(params)
            pad = self._flat_meta[4]
            master = jnp.concatenate(
                [jnp.ravel(v).astype(jnp.float32) for v in leaves]
                + ([jnp.zeros((pad,), jnp.float32)] if pad else []))
            mu = jnp.zeros(master.shape, mdt)
            nu = jnp.zeros(master.shape, mdt)
            return TrainState(params, master, mu, nu, step)
        # copy=True: when params are already fp32, astype would alias the
        # same buffer and double-donation breaks Execute()
        master = jax.tree_util.tree_map(
            lambda v: jnp.array(v, dtype=jnp.float32, copy=True), params)
        master = shard(master)
        mu = jax.tree_util.tree_map(
            lambda v: jnp.zeros(v.shape, mdt), master)
        nu = jax.tree_util.tree_map(
            lambda v: jnp.zeros(v.shape, mdt), master)
        mu, nu = shard(mu), shard(nu)
        return TrainState(params, master, mu, nu, step)

    # -- compiled step -------------------------------------------------------
    def _build(self):
        from ..ops.pallas._util import gspmd_program
        hp = self.hp

        def step_fn(state_tree, lr, *batch):
            # GSPMD partitions this program over the mesh; on more than
            # one device a Mosaic kernel cannot be lowered into it, so
            # kernel routing takes the compositions for this trace
            with gspmd_program(self.mesh.devices.size):
                return step_body(state_tree, lr, *batch)

        def step_body(state_tree, lr, *batch):
            params = state_tree[0]

            def loss_of(p, *b):
                return self.loss_fn(p, *b)

            if self.accumulate_steps > 1:
                # micro-batch gradient accumulation via scan over the
                # leading accumulation axis
                def micro(carry, mb):
                    loss, g = jax.value_and_grad(loss_of)(params, *mb)
                    acc_loss, acc_g = carry
                    return (acc_loss + loss,
                            jax.tree_util.tree_map(jnp.add, acc_g, g)), None
                zero_g = jax.tree_util.tree_map(
                    lambda v: jnp.zeros(v.shape, jnp.float32), params)
                (tot_loss, grads), _ = jax.lax.scan(
                    micro, (jnp.zeros((), jnp.float32), zero_g), batch)
                n = self.accumulate_steps
                loss = tot_loss / n
                with jax.named_scope("optimizer/grads"):
                    grads = jax.tree_util.tree_map(lambda g: g / n, grads)
            else:
                loss, grads = jax.value_and_grad(loss_of)(params, *batch)
            if self._fused:
                new_state, gnorm = self._fused_update(grads, state_tree, lr)
            else:
                new_state, gnorm = _adamw_update(
                    grads, state_tree, lr, b1=hp["b1"], b2=hp["b2"],
                    eps=1e-8, wd=hp["wd"], grad_clip=hp["grad_clip"])
                self._optimizer_variant = {"variant": "per_leaf",
                                           "block": None}
            metrics = {"loss": loss, "grad_norm": gnorm}
            if nan_check:
                # FLAGS_check_nan_inf inside the compiled hybrid-parallel
                # step (loss + grad-norm covers every grad contribution)
                metrics["finite"] = jnp.isfinite(loss) & jnp.isfinite(gnorm)
            return new_state, metrics

        from ..core.flags import GLOBAL_FLAGS
        nan_check = bool(GLOBAL_FLAGS.get("check_nan_inf"))
        # no donation in nan-check mode: on failure the caller's pre-step
        # state must survive the raise (donated inputs are invalidated)
        donate = (0,) if self._donate and not nan_check else ()
        self._step_nan = nan_check
        self._step_fused = _fused_train_key()
        self._step_fn = jax.jit(step_fn, donate_argnums=donate)
        self._noted = False
        if self._compiled_cache is not None:
            # the program changed (nan-check flag flip): cached AOT
            # executables compile against the OLD step_fn
            self._compiled_cache.clear()

    def _fused_update(self, grads, state_tree, lr):
        """Single-pass Pallas AdamW over flat fp32 state (+ bf16 shadow).
        grads arrive as a pytree; one concat (the only extra HBM traffic)
        feeds the multi-tensor kernel, and the updated shadow is sliced
        back into the param tree shapes. The kernel is registry-
        dispatched (``adamw_update``): the Pallas multi-tensor kernel
        on TPU, the jnp composition of the same arithmetic under
        interpret mode — the dispatch inputs are covered by
        ``_fused_train_key``; what it picked while the step traced is
        ``optimizer_variant``."""
        from ..ops.pallas.fused_adamw import (LANES, adamw_update,
                                              variant_record)
        from ..ops.pallas.registry import KERNELS
        hp = self.hp
        treedef, shapes, sizes, pdtype, pad, dtypes = self._flat_meta
        _, master, mu, nu, step = state_tree
        step_n = step + 1
        g_leaves = jax.tree_util.tree_leaves(grads)
        # concat dtype: the low-precision dtype ONLY when every grad
        # already carries it (lossless, halves the flat grad's HBM).
        # A mixed tree concats in fp32 — truncating the fp32 leaves'
        # grads to bf16 would break the exactness the fp32-master
        # slice-back promises and skew the global clip norm.
        leaf_dts = {g.dtype for g in g_leaves}
        gdt = (pdtype if pdtype is not None
               and leaf_dts == {jnp.dtype(pdtype)} else jnp.float32)
        # the named scopes are observability.PROGRAM_SCOPES: a reader of
        # a device trace finds each operation's by them (metadata only)
        with jax.named_scope("optimizer/grads"):
            g_flat = jnp.concatenate(
                [jnp.ravel(g).astype(gdt) for g in g_leaves]
                + ([jnp.zeros((pad,), gdt)] if pad else []))
        with jax.named_scope("optimizer/clip"):
            gnorm = jnp.sqrt(jnp.sum(jnp.square(
                g_flat.astype(jnp.float32))))
            scale = jnp.minimum(1.0, hp["grad_clip"]
                                / jnp.maximum(gnorm, 1e-12)) \
                if hp["grad_clip"] else jnp.float32(1.0)
        with KERNELS.record() as picked, \
                jax.named_scope("optimizer/update"):
            outs = adamw_update(
                master, g_flat, mu, nu, lr, step_n.astype(jnp.float32),
                beta1=hp["b1"], beta2=hp["b2"], epsilon=1e-8,
                weight_decay=hp["wd"], grad_scale=scale,
                shadow_dtype=pdtype)
        self._optimizer_variant = variant_record(picked, master.shape[0])
        if pdtype is not None:
            master_n, mu_n, nu_n, shadow = outs
        else:
            master_n, mu_n, nu_n = outs
            shadow = master_n
        # fp32 leaves come back exact from the master; the rest from
        # the single low-precision shadow written in the same pass
        flat = {True: master_n, False: shadow}
        # ... as rows of the launch's own (rows, 128) view (a bitcast of
        # the flat vector) where a leaf is whole rows: XLA turns a slice
        # of the FLAT fp32 master into a re-layout of the whole master
        # (``f32[n / 4096, 4096] reshape``: 8 ms a step at the training
        # cell), then slices that
        leaves, off = [], 0
        with jax.named_scope("optimizer/params_out"):
            rows = {k: v.reshape(-1, LANES) for k, v in flat.items()}
            for shp, sz, dt in zip(shapes, sizes, dtypes):
                exact = dt == jnp.dtype(jnp.float32)
                if off % LANES == 0 and sz % LANES == 0:
                    leaf = jax.lax.slice(rows[exact], (off // LANES, 0),
                                         ((off + sz) // LANES, LANES))
                else:
                    leaf = jax.lax.slice(flat[exact], (off,), (off + sz,))
                leaves.append(leaf.reshape(shp))
                off += sz
        params_n = jax.tree_util.tree_unflatten(treedef, leaves)
        return (params_n, master_n, mu_n, nu_n, step_n), gnorm

    def _stage_batch(self, b):
        """device_put only when needed. Re-putting an already-placed
        array (or minting a fresh host scalar) every step costs a
        blocking h2d roundtrip per call, which serializes the step
        behind host latency. A device array whose sharding already
        matches passes straight through to the compiled call."""
        if not (hasattr(b, "ndim") and b.ndim >= 2):
            return b
        target = NamedSharding(self.mesh, self.data_spec)
        if isinstance(b, jax.Array):
            try:
                if b.sharding.is_equivalent_to(target, b.ndim):
                    return b
            except Exception:  # noqa: BLE001 — conservative: fall through
                pass
        return jax.device_put(b, target)

    def prefetch(self, batches, depth: int = 2):
        """Double-buffered ingest (reference:
        python/paddle/io/dataloader/dataloader_iter.py:368 buffer
        reader): yields batches already staged onto the mesh with the
        trainer's data sharding while the NEXT batch's h2d transfer runs
        behind the CURRENT step's compute, so steady-state step time is
        max(compute, transfer) instead of compute + transfer. ``batches``
        yields a tuple/list per step (the ``*batch`` of :meth:`step`) or
        a single array. With observability on, each pull samples the
        staged-queue depth as a gauge — a queue pinned at 0 means the
        consumer is ingest-bound, at ``depth`` compute-bound."""
        from ..io.dataloader import _DevicePrefetchIter

        def stage(b):
            if isinstance(b, (tuple, list)):
                return tuple(self._stage_batch(x) for x in b)
            return self._stage_batch(b)

        on_next = None
        obs = self._obs
        if obs is not None:
            def on_next(qsize):
                obs.registry.gauge("prefetch_queue_depth",
                                   obs.gauge_window).set(qsize, obs.now())

        return _DevicePrefetchIter(iter(batches), stage,
                                   depth=max(1, depth), on_next=on_next)

    # the trainer's OWN counter keys: reset_metrics()/metrics() touch
    # exactly these — the counters dict is adopted by the registry and
    # a bound flight recorder stores its dict-valued collective
    # counters in the same dict, which a blanket zero would destroy
    _COUNTER_KEYS = ("steps", "samples", "tokens")

    def _count_step(self, batch, t_end: float):
        """Throughput bookkeeping shared by both step paths: samples =
        leading batch dims, tokens = full element count of the first
        batch array (covers the (acc, B, S) accumulation layout)."""
        self.counters["steps"] += 1
        b0 = batch[0] if batch else None
        shape = getattr(b0, "shape", None)
        if shape:
            if len(shape) >= 2:
                self.counters["samples"] += int(np.prod(shape[:-1]))
                self.counters["tokens"] += int(np.prod(shape))
            else:
                self.counters["samples"] += int(shape[0])
        self._t_last = t_end

    def step(self, state: TrainState, *batch) -> Tuple[TrainState, Dict]:
        from ..core.flags import GLOBAL_FLAGS
        if self._step_fn is None or \
                self._step_nan != bool(GLOBAL_FLAGS.get("check_nan_inf")) \
                or self._step_fused != _fused_train_key():
            self._build()
        if self._obs is not None:
            out = self._step_observed(state, batch)
            if self._telemetry is not None:
                self._telemetry.on_step()
            return out
        if self._t_first is None:
            self._t_first = time.perf_counter()
        with span("train/stage"):
            batch = tuple(self._stage_batch(b) for b in batch)
        if getattr(self, "_lr_cache", None) is None or \
                self._lr_cache[0] != self.lr:
            # one h2d when lr changes, not one per step
            self._lr_cache = (self.lr, jnp.float32(self.lr))
        with span("train/dispatch"), self.mesh:
            args = (state.tree(), self._lr_cache[1], *batch)
            if not self._noted and tracing():
                self._note(self._step_fn, args)
            new_tree, metrics = self._step_fn(*args)
        self._count_step(batch, time.perf_counter())
        if "finite" in metrics and not bool(metrics.pop("finite")):
            raise FloatingPointError(
                "check_nan_inf: non-finite loss/grad_norm in compiled "
                f"train step (loss={float(metrics['loss'])})")
        return TrainState.from_tree(new_tree), metrics

    # -- observed step (enabled mode) ---------------------------------------
    def _compiled_for(self, tree, lr, staged):
        """AOT executable for this abstract input signature, compiled
        (and telemetered) once per signature through the CompileWatcher.
        A signature miss after :meth:`reset_metrics` armed the watcher
        is a steady-state retrace and warns — the train-loop analog of
        the serving retrace watchdog. Returns ``(fn, compile_ms)`` so
        the caller can attribute compile time to its own histogram
        instead of the dispatch phase. The key hashes (treedef, shape,
        dtype object, sharding) — dtype objects, not strings:
        re-stringifying every leaf of a large param tree per step would
        be unattributed host overhead in exactly the layer built to
        surface it. The SHARDING must be in the key: on a multi-device
        mesh GSPMD propagation may re-shard state leaves in the step-1
        OUTPUT (norm weights, gate/up_proj), and an executable compiled
        for the step-0 shardings rejects the changed inputs at step 2
        ("input sharding(s) does not match") where plain jit reshards
        silently. Keyed on sharding, step 2 is a cache miss and
        ``lower()`` carries the COMMITTED shardings in — one extra
        warmup compile, then a stable program (GSPMD reaches its fixed
        point at the propagated layout)."""
        leaves, treedef = jax.tree_util.tree_flatten((tree, lr) + staged)
        key = (treedef,
               tuple((getattr(v, "shape", ()), getattr(v, "dtype", None),
                      _sharding_cache_key(v))
                     for v in leaves))
        fn = self._compiled_cache.get(key)
        if fn is not None:
            return fn, 0.0
        rec = self._compile
        fn = rec.compile("train_step", self._step_fn, tree, lr, *staged)
        self._compiled_cache[key] = fn
        # feed the static analyzer's registry (only on a compile, so
        # zero steady-state cost): the first compile REGISTERS this
        # trainer's spec, later compiles record their signatures into
        # it — a second distinct signature is what the retrace-hazard
        # rule reports as MULTIPLE_SIGNATURES. Recording is gated on
        # spec.fn being THIS step_fn: another trainer (or the audit
        # catalog) owning the name must not inherit our signatures.
        try:
            from ..analysis import REGISTRY as _AREG
            spec = _AREG.get("train_step")
            if spec is None or spec.fn is not self._step_fn:
                _AREG.register(self._build_audit_spec(tree, lr, staged))
            else:
                from ..analysis import abstract_signature as _abs
                spec.record_signature(tuple(_abs((tree, lr) + staged)),
                                      {})
        except Exception:  # noqa: BLE001 — telemetry must never raise
            pass
        return fn, rec.programs["train_step"]["wall_s_last"] * 1e3

    def _step_observed(self, state: TrainState, batch
                       ) -> Tuple[TrainState, Dict]:
        """The enabled-mode step: same program, phase-timed. Runs the
        identical jitted ``step_fn`` through ``lower().compile()`` (the
        HLO is the same, so loss/grad_norm stay bit-identical to the
        disabled path) and splits the wall time into stage (batch h2d),
        dispatch (compiled call returning) and sync (the wait for the
        device) — the split the host-vs-device gap detector reads."""
        obs = self._obs
        if self._t_first is None:
            self._t_first = obs.now()
        with span("train/stage", obs, hist="stage_ms",
                  ring=False) as stage:
            staged = tuple(self._stage_batch(b) for b in batch)
        with span("train/dispatch", obs, ring=False) as disp, self.mesh:
            if getattr(self, "_lr_cache", None) is None or \
                    self._lr_cache[0] != self.lr:
                self._lr_cache = (self.lr, jnp.float32(self.lr))
            tree = state.tree()
            if self._aot_fallback:
                # a previous sharding mismatch demoted this trainer to
                # the plain jit path (one-time warning below): same
                # program, jit reshards silently; compile telemetry is
                # whatever the watcher recorded before the demotion
                compile_ms = 0.0
                new_tree, metrics = self._step_fn(
                    tree, self._lr_cache[1], *staged)
            else:
                fn, compile_ms = self._compiled_for(
                    tree, self._lr_cache[1], staged)
                if not self._noted and tracing():
                    self._note(fn, None)    # the executable itself
                try:
                    new_tree, metrics = fn(tree, self._lr_cache[1],
                                           *staged)
                except ValueError as e:
                    # the sharding-aware cache key above should make
                    # this unreachable; if a backend still rejects the
                    # committed shardings, degrade to the jit path
                    # cleanly instead of killing the train loop
                    if "sharding" not in str(e):
                        raise
                    import warnings
                    warnings.warn(
                        "observed train step: AOT executable rejected "
                        f"the committed input shardings ({e}); falling "
                        "back to the plain jit path for this trainer "
                        "(phase timings stay, compile telemetry "
                        "freezes)", RuntimeWarning, stacklevel=2)
                    self._aot_fallback = True
                    new_tree, metrics = self._step_fn(
                        tree, self._lr_cache[1], *staged)
        with span("train/sync", obs, hist="sync_ms", ring=False) as sync:
            jax.block_until_ready(metrics)
        t_sync = obs.now()
        stage_ms, sync_ms = stage.dur_ms, sync.dur_ms
        # dispatch = key-build + cache lookup + the compiled call
        # returning; a compile this step is timed by the watcher and
        # excluded here rather than masquerading as dispatch work
        dispatch_ms = max(disp.dur_ms - compile_ms, 0.0)
        step_ms = stage_ms + disp.dur_ms + sync_ms
        self._count_step(batch, t_sync)
        step_idx = self.counters["steps"]
        obs.hist("step_ms").observe(step_ms)
        obs.hist("dispatch_ms").observe(dispatch_ms)
        loss = float(metrics["loss"])
        gnorm = float(metrics["grad_norm"])
        vals = {"loss": loss, "grad_norm": gnorm}
        hbm = live_hbm_bytes(self.mesh.devices.flat[0])
        if hbm is not None:
            vals["hbm_bytes_in_use"] = hbm
        obs.sample_gauges(t_sync, vals)
        obs.timeline.record(
            "train_step", dur_ms=step_ms, step=step_idx,
            stage_ms=round(stage_ms, 3),
            dispatch_ms=round(dispatch_ms, 3),
            sync_ms=round(sync_ms, 3), loss=round(loss, 6))
        finding = self._gap.observe(step_idx, stage_ms, dispatch_ms,
                                    sync_ms)
        if finding is not None:
            obs.timeline.record("host_gap", **finding)
            if self._gap.should_dump():
                obs.stall_dump(
                    f"host-vs-device gap: step {step_idx} spent "
                    f"{finding['host_ms']:.1f} ms on the host "
                    f"(stage {finding['stage_ms']:.1f} + dispatch "
                    f"{finding['dispatch_ms']:.1f}) vs "
                    f"{finding['device_wait_ms']:.1f} ms waiting on "
                    "the device — per-step h2d staging or host-side "
                    "work owns this step, not compute",
                    scheduler={"phase_split": finding,
                               "mesh": {str(k): int(v) for k, v
                                        in self.mesh.shape.items()},
                               "accumulate_steps": self.accumulate_steps},
                    metrics={"steps": step_idx})
        if obs.step_deadline_s is not None \
                and step_ms > obs.step_deadline_s * 1e3:
            obs.stall_dump(
                f"train step {step_idx} took {step_ms:.1f} ms "
                f"(deadline {obs.step_deadline_s * 1e3:.1f} ms)",
                scheduler={"step": step_idx,
                           "phases": {"stage_ms": round(stage_ms, 3),
                                      "dispatch_ms": round(dispatch_ms, 3),
                                      "sync_ms": round(sync_ms, 3)}})
        if "finite" in metrics and not bool(metrics.pop("finite")):
            raise FloatingPointError(
                "check_nan_inf: non-finite loss/grad_norm in compiled "
                f"train step (loss={loss})")
        return TrainState.from_tree(new_tree), metrics

    # -- static program audit -----------------------------------------------
    def _build_audit_spec(self, tree, lr, batch):
        """The ONE definition of the train step's ProgramSpec (shared
        by :meth:`audit_spec` and the observed step's compile hook, so
        carry/donation metadata cannot drift between them): abstract
        signature, the state-leaf carry map (new state out feeds state
        in next call — the contract whose dtype drift was the AdamW
        x64 bug), declared donation, and the mesh axis names."""
        from ..analysis import ProgramSpec, abstract_signature
        n_state = len(jax.tree_util.tree_leaves(tree))
        return ProgramSpec(
            name="train_step", fn=self._step_fn,
            args=tuple(abstract_signature((tree, lr) + tuple(batch))),
            donate_argnums=(0,) if self._donate else (),
            carry={i: i for i in range(n_state)},
            mesh_axes=tuple(str(a) for a in self.mesh.axis_names),
            tags=("trainer",))

    def audit_spec(self, state: TrainState, *batch, register: bool = True):
        """Build the :class:`paddle_tpu.analysis.ProgramSpec` for the
        compiled train step at THIS state/batch signature (no buffers
        captured). ``register=True`` also files it in the global
        analysis registry so ``tools/program_audit.py`` sees it."""
        from ..analysis import REGISTRY
        from ..core.flags import GLOBAL_FLAGS
        if self._step_fn is None or \
                self._step_nan != bool(GLOBAL_FLAGS.get("check_nan_inf")) \
                or self._step_fused != _fused_train_key():
            self._build()
        spec = self._build_audit_spec(state.tree(),
                                      jnp.float32(self.lr), batch)
        if register:
            REGISTRY.register(spec)
        return spec

    def audit(self, state: TrainState, *batch, register: bool = True):
        """Static program audit of the train step (trace-only, nothing
        executes, the jit cache is untouched): runs the
        ``paddle_tpu.analysis`` rule passes — dtype promotion, donation,
        retrace hazards, collective consistency, constant bloat — and
        returns the :class:`AuditReport`. Findings land in the
        ``audit_findings`` counter (and the timeline, when
        observability is on)."""
        from ..analysis import audit_spec as _audit, publish_findings
        spec = self.audit_spec(state, *batch, register=register)
        with self.mesh:
            report = _audit(spec)
        publish_findings(report, counters=self.counters, obs=self._obs)
        return report

    # -- metrics / export ---------------------------------------------------
    @property
    def observability(self) -> Optional[Observability]:
        return self._obs

    def _require_obs(self) -> Observability:
        if self._obs is None:
            raise RuntimeError(
                "observability is disabled for this trainer; construct "
                "with Trainer(..., observability=True)")
        return self._obs

    def _note(self, fn, args):
        """Hand the step to the registry of compiled programs, with the
        arguments the dispatch is about to donate."""
        self._noted = True
        self._program_keys.append(_programs.note(fn, args))

    def program_scopes(self):
        """The compiled step as a reader of a device trace needs it
        (``observability.programs.Program``: ``{instruction name:
        scope}``; ``ServingEngine.program_scopes`` is the same for the
        serving programs). Parsed on the first call, never on a step."""
        return _programs.scopes(self._program_keys)

    @property
    def optimizer_variant(self) -> Dict:
        """Which optimizer the compiled step holds: ``{"variant": ...,
        "block": ...}`` — ``"pallas_fused"`` with the ``[R, W]`` blocks
        the launch streams the flat state in, ``"unfused"`` (XLA's own
        fusion over the flat state) or ``"per_leaf"`` (no flat state),
        both without blocks. It IS the registry's record of the
        dispatch made while the step traced (``KERNELS.record``, as
        ``ServingEngine.decode_variant``), so it cannot drift from the
        compiled program. Before the first step the names are None."""
        return dict(self._optimizer_variant
                    or {"variant": None, "block": None})

    def metrics(self) -> Dict:
        """Training telemetry snapshot. Base keys (both modes): step /
        sample / token counters and throughput over the current window.
        With observability on: per-step phase histograms, gauges,
        compile telemetry (count, wall time, cost/memory analysis),
        cost-analysis-derived MFU, the train-step HBM breakdown, and
        the host-gap / stall-dump / timeline counters.

        Caveat (disabled mode only): the window closes at async
        dispatch return — without a sync the device may still be
        executing, so tokens/samples-per-sec are upper bounds unless
        the caller reads a metric (``float(m["loss"])``) before
        snapshotting. The observed step syncs per step, so its window
        is exact."""
        c = {k: self.counters[k] for k in self._COUNTER_KEYS}
        wall = ((self._t_last - self._t_first)
                if self._t_first is not None and self._t_last is not None
                else 0.0)
        c["wall_time_s"] = round(wall, 6)
        c["samples_per_sec"] = (round(c["samples"] / wall, 3)
                                if wall > 0 else 0.0)
        c["tokens_per_sec"] = (round(c["tokens"] / wall, 3)
                               if wall > 0 else 0.0)
        c["optimizer_variant"] = self.optimizer_variant
        if "audit_findings" in self.counters:
            # conditional key (the prefix_cache idiom): present only
            # after a static program audit ran against this trainer
            c["audit_findings"] = self.counters["audit_findings"]
        if self._obs is None:
            return c
        obs = self._obs
        c["latency"] = obs.latency_snapshot(TRAIN_HISTOGRAMS)
        c["gauges"] = obs.gauges_snapshot()
        comp = self._compile.snapshot()
        c["compile"] = comp
        c["compiles"] = comp["count"]
        c["retrace_warnings"] = comp["retraces_after_warmup"]
        c["mfu"] = self._compile.mfu("train_step", steps=c["steps"],
                                     wall_s=wall)
        prog = self._compile.programs.get("train_step")
        c["hbm"] = prog.get("memory") if prog else None
        c["host_gap_findings"] = len(self._gap.findings)
        c["stall_dumps"] = (len(obs.stall_dumps)
                            + obs.stall_dumps_suppressed)
        c["timeline_events"] = len(obs.timeline)
        c["timeline_dropped"] = obs.timeline.dropped
        # a bound flight recorder parks per-(op, axis) call/byte
        # counters in the shared dict and latency histograms in the
        # registry; surface both as one sub-dict (conditional key, the
        # prefix_cache idiom) — the histograms would otherwise be dead
        # data reachable only by poking registry internals
        calls = self.counters.get("collective_calls")
        if calls:
            c["collectives"] = {
                "calls": dict(calls),
                "bytes": dict(self.counters.get("collective_bytes", {})),
                "latency_ms": {
                    name[len("collective_"):-len("_ms")]: h.snapshot()
                    for name, h in sorted(
                        obs.registry.histograms.items())
                    if name.startswith("collective_")
                    and name.endswith("_ms")}}
        if self._telemetry is not None:
            c["telemetry"] = self._telemetry.snapshot()
        return c

    @property
    def telemetry(self) -> Optional[TelemetryPlane]:
        """The continuous telemetry plane, or None when disabled."""
        return self._telemetry

    def _telemetry_alert(self, alert: Dict):
        """Stamp an ``alert`` timeline event; page-severity alerts also
        land a flight-recorder dump (the trainer has no scheduler, so
        the dump carries the throughput counters instead)."""
        obs = self._obs
        if obs is None:
            return
        obs.timeline.record(
            "alert", rule=alert.get("rule"),
            severity=alert.get("severity"), metric=alert.get("metric"),
            value=alert.get("value"), threshold=alert.get("threshold"))
        if (alert.get("severity") == "page"
                and self._telemetry.config.page_dumps):
            obs.stall_dump(
                f"telemetry alert: {alert.get('rule')} on "
                f"{alert.get('metric')}",
                {"counters": {k: self.counters[k]
                              for k in self._COUNTER_KEYS}},
                metrics={"alert": alert})

    def reset_metrics(self):
        """Zero the throughput window (e.g. after compile warmup).
        With observability on this also restarts the histogram window
        and ARMS the compile watcher: any train-step compile after this
        call is a steady-state retrace and warns — the trainer analog
        of the serving ``reset_metrics()`` watchdog contract. Only the
        trainer's own counter keys reset — a bound flight recorder's
        collective counters in the shared dict survive."""
        for k in self._COUNTER_KEYS:
            self.counters[k] = 0
        self._t_first = self._t_last = None
        if self._obs is not None:
            self._obs.reset_window()
            self._compile.arm()
            # warmup's first-staging host gap must neither show up in
            # the measured window's findings nor spend its dump budget
            # (the PR-3 warmup-exclusion contract); already-written
            # dump FILES stay counted — retention is about disk
            self._gap.reset()

    def export_trace(self, path: str) -> str:
        """Write the per-step chrome trace (train_step/compile spans +
        gauge counter tracks + any bound flight-recorder collective
        tracks) — open in Perfetto / chrome://tracing."""
        return self._require_obs().export_chrome(
            path, process_name="paddle_tpu trainer")

    def write_timeline(self, path: str) -> str:
        """Write the structured per-step JSONL — input for
        ``tools/trace_summary.py --mode train``."""
        return self._require_obs().write_jsonl(
            path, header={"mode": "train",
                          "mesh": {str(k): int(v)
                                   for k, v in self.mesh.shape.items()},
                          "accumulate_steps": self.accumulate_steps})
