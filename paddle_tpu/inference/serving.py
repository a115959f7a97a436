"""Continuous-batching serving engine over the paged-KV cache.

``generate_paged`` runs STATIC batches: every prompt prefills together
and the whole batch drains at the pace of its slowest request, so real
mixed-arrival traffic leaves decode slots idle and queues new requests
behind the entire batch (head-of-line blocking). This module is the
scheduler the paged building blocks (``ops.paged_attention``'s pools +
``BlockManager``) were missing — vLLM-style continuous batching, the
TPU analog of the reference's AnalysisPredictor serving loop around
``fusion/block_multihead_attention``:

- a fixed-capacity SLOT TABLE: every decode step is ONE jitted program
  over all ``capacity`` slots. Inactive slots are padded — seq_len 0,
  block table pointing at the reserved scratch page — so admission and
  completion never change shapes: steady state is zero retraces.
- BUCKETED CHUNKED PREFILL: a new request's prompt runs through
  per-bucket jitted programs in bounded chunks (each at most the
  largest bucket), interleaved with in-flight decode steps. Each chunk
  gathers the request's pages into a dense view, runs the same
  ``cached_forward`` math as ``generate``'s prefill, and scatters the
  updated pages back — at most one trace per bucket, ever.
- SLOT RECYCLING: a finished request releases its KV pages back to the
  ``BlockManager`` and its slot is immediately re-admitted from the
  queue at the next step.
- int8 cache (``cache_dtype="int8"``): pools store int8 with static
  per-layer-per-head scales calibrated once from the first admitted
  prompt (the same calibration point as ``generate_paged``); prefill
  dequants pages into the chunk's dense view and requantizes on the way
  out (idempotent for untouched positions, same scale), decode runs the
  quantized gather path.
- RADIX PREFIX CACHE (``prefix_cache=True``): finished requests return
  their KV pages to a radix tree (inference/prefix_cache.py) instead of
  freeing them; admission longest-prefix-matches the prompt so a warm
  request appends the shared pages to its block table and prefills only
  its un-cached suffix. Prefill programs take a separate WRITE table
  whose shared-prefix entries are redirected to the scratch page, so a
  shared page is never written by construction; the partially-filled
  tail page is handed out only as a copy-on-write fork. The tree evicts
  LRU refcount-1 pages on allocator pressure. Programs keep the exact
  shapes of the cold path: cache hits cause zero retraces, and because
  the engine's int8 scales are engine-global and static, the int8 cache
  participates in sharing unchanged.

- TENSOR PARALLELISM (``mesh=ServingMesh(...)``, inference/tp.py): the
  paged KV pools, the QKV/o-proj/MLP weights and the per-slot attention
  computation shard along the HEAD axis of a named 1-D mesh via
  shard_map; the decode step stays ONE jitted program (sampling runs on
  the replicated logits), bucketed prefill stays <=1 trace per bucket,
  and the page tables stay host-global so BlockManager/prefix-cache
  logic is identical. Collective placement and the greedy-parity
  contract (bit-identical for collective="gather", roundoff for the
  default "psum") are documented in inference/tp.py.

Host/device split: the decode carry (tokens, seq_lens, key, pools)
stays device-resident between steps; host mirrors are re-uploaded only
when admission state changes. The per-step device->host read of the
sampled tokens is the scheduling point where the host detects EOS /
length-done and recycles slots.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..core.jax_compat import shard_map_norep
from ..observability import (Observability, TelemetryConfig,
                             TelemetryPlane, programs as _programs, span,
                             tracing)
from ..ops.paged_attention import (BlockManager, dequant_cache,
                                   quant_cache)
from .admission import AdmissionQueue
from .generation import (GenerationConfig, _decode_step,
                         _fused_prefill_forward, _fused_prefill_mode,
                         _prefill_route, cached_forward, init_cache,
                         kernel_route)

__all__ = ["Request", "ServingEngine"]

_SCRATCH_SEQ = -1      # BlockManager key owning the reserved page 0


def _sample_slots(logits, key, temps):
    """[C, V] logits -> [C] next tokens. ``temps[i] <= 0`` selects
    greedy for that slot; otherwise temperature sampling — per-request
    sampling rides as a traced array, so mixing greedy and sampled
    requests in one batch costs no retrace."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1)
    sampled = jax.random.categorical(
        key, logits / jnp.maximum(temps, 1e-6)[:, None], axis=-1)
    return jnp.where(temps <= 0.0, greedy, sampled).astype(jnp.int32)


def _collectives_snapshot(counters: Dict, obs: Observability) -> Dict:
    """The structured ``metrics()["collectives"]`` sub-dict (the
    Trainer.metrics contract): per-(op, axis) call/byte counters from
    the adopted dict + latency histograms from the bound recorder.
    ONE definition shared by ServingEngine and DisaggregatedEngine."""
    return {"calls": dict(counters.get("collective_calls", {})),
            "bytes": dict(counters.get("collective_bytes", {})),
            "latency_ms": {
                name[len("collective_"):-len("_ms")]: h.snapshot()
                for name, h in sorted(obs.registry.histograms.items())
                if name.startswith("collective_")
                and name.endswith("_ms")}}


def _drain_loop(eng, max_steps: Optional[int], starve_reason: str,
                starve_error: str) -> int:
    """The shared drain loop (ServingEngine and DisaggregatedEngine):
    step until idle; a capped drain records truncation; a step that
    can run nothing while work is pending raises, after a stall dump —
    unless the engine went idle during that step (e.g. its only
    remaining request deadline-expired), which is a clean finish."""
    n = 0
    eng.last_drain_truncated = False
    while not eng.idle:
        if not eng.step():
            if eng.idle:
                break       # the last step only expired/cleaned up
            dump = ""
            if eng._obs is not None:
                dump = eng._obs.stall_dump(starve_reason,
                                           eng.scheduler_snapshot(),
                                           metrics=eng.metrics())
            raise RuntimeError(
                starve_error + (f"; stall dump: {dump}" if dump else ""))
        n += 1
        if max_steps is not None and n >= max_steps:
            if not eng.idle:
                eng.last_drain_truncated = True
                eng.counters["drain_truncations"] += 1
                eng._drain_truncated_event(n)
            break
    return n


@dataclass
class Request:
    """One serving request and its lifecycle record."""
    req_id: int
    prompt: np.ndarray                       # [S] int32
    gen: GenerationConfig
    submit_t: float = 0.0
    priority: int = 1                        # class, LOWER = more urgent
    deadline_s: Optional[float] = None       # admission SLO (vs submit)
    tokens: List[int] = field(default_factory=list)   # generated ids
    ttft: Optional[float] = None             # sec, first token - submit
    admit_t: Optional[float] = None          # absolute, perf_counter
    first_token_t: Optional[float] = None    # absolute, perf_counter
    finish_t: Optional[float] = None
    done: bool = False
    expired: bool = False                    # deadline passed in queue
    preemptions: int = 0
    # (seq_len, last sampled token): set when the request holds valid
    # KV pages but no slot — a preempted decode slot awaiting requeue,
    # or a disaggregated handoff entering the decode group. Admission
    # re-enters decode directly from this carry; because the values are
    # exactly the ones the vacated slot held, the resumed decode is
    # bit-identical to the un-preempted run.
    resume: Optional[Tuple[int, int]] = None
    # the request's live admission-queue entry (engine bookkeeping):
    # set at push, reused by preemption's requeue so the victim keeps
    # its original line position and requeue count
    qentry: Optional[object] = field(default=None, repr=False)

    @property
    def output_ids(self) -> np.ndarray:
        return np.concatenate([np.asarray(self.prompt, np.int32),
                               np.asarray(self.tokens, np.int32)])


class _Slot:
    __slots__ = ("req", "phase", "seq_len", "prefill_pos")

    def __init__(self):
        self.req: Optional[Request] = None
        self.phase = "idle"          # idle | prefill | decode
        self.seq_len = 0             # tokens cached in the pools
        self.prefill_pos = 0         # next prompt position to prefill


class ServingEngine:
    """Continuous-batching engine over a shared paged KV pool.

    ``submit()`` enqueues a request; ``step()`` runs one scheduler
    iteration (admit -> one prefill chunk -> one decode step over all
    live slots); ``drain()`` steps until idle. ``metrics()`` reports
    tokens/s, TTFT, decode-slot utilization and compile/trace counts.

    ``observability=True`` (or an ``Observability`` instance) threads
    the metrics/tracing harness through the scheduler: per-request
    lifecycle events in a bounded ring buffer, TTFT/TPOT/queue-wait
    p50/p95/p99 histograms, per-step allocator + prefix-cache gauges,
    a retrace watchdog armed by ``reset_metrics()``, and flight-
    recorder stall dumps on ``drain()`` starvation or a blown
    ``step_deadline_s``. ``export_trace(path)`` writes a chrome trace,
    ``write_timeline(path)`` the structured per-phase JSONL. All hooks
    are host-side timestamps — greedy output, program shapes and the
    single per-step device sync are unchanged.
    """

    def __init__(self, params: Dict, cfg, capacity: int = 4,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 max_seq_len: Optional[int] = None, cache_dtype=None,
                 prefill_buckets=(32, 128), seed: int = 0,
                 prefix_cache: bool = False, kv_offload=False,
                 observability=False, mesh=None,
                 fused_prefill=None, weight_quant=None,
                 aging_s: Optional[float] = None, telemetry=False,
                 clock=None, state_dtype=None):
        # a model that is run by its layer pattern (models/pattern.py)
        # keeps more per-request state than one class of pages: a
        # recurrent state a slot, or a second class of pages that go
        # back behind a sliding window. What the engine needs of such a
        # model it reads from ONE description (inference/hybrid.py
        # ServedPattern; None for the dense decoder); what cannot serve
        # it yet is refused here, by the mechanism that is missing
        from .hybrid import served_pattern
        self._pattern = pat = served_pattern(cfg)
        if pat is not None:
            pat.refuse(mesh=mesh, weight_quant=weight_quant,
                       cache_dtype=cache_dtype, kv_offload=kv_offload)
            fused_prefill = False
        if state_dtype is not None and not (pat and pat.recurrent_layers):
            raise ValueError("state_dtype is the recurrent state's type: "
                             f"{type(cfg).__name__} has no recurrent layer")
        # tensor parallelism (inference/tp.py): a ServingMesh shards
        # the KV pools, projections and per-slot attention along the
        # head axis; programs wrap in shard_map. None = single device.
        # Accepts a ServingMesh, a 1-D jax Mesh, or an int tp degree.
        from ..quantization.ptq import ensure_quantized
        from .tp import normalize_mesh
        # injectable scheduler clock (the admission queue's idiom, now
        # engine-wide): every scheduling timestamp — submit_t, expiry,
        # aging, admit/finish times — reads THIS callable, so tests and
        # the lifecycle model checker (analysis/lifecycle.py) can drive
        # admission deadlines and aging deterministically. None = wall
        # clock (time.perf_counter), behavior unchanged.
        self._clock = clock if clock is not None else time.perf_counter
        # opt-in per-step structural self-check: the lifecycle model
        # checker's manager+cache invariant set (BlockManager.check /
        # PrefixCache.check) asserted after every step. Off by default
        # (it walks the tree and the page pool each step).
        self._check_inv = os.environ.get(
            "PADDLE_TPU_CHECK_INVARIANTS", "") == "1"
        # weight quantization (quantization/ptq.py): "int8"/"int4"
        # quantizes a plain fp tree in ONE shot (host-side per-channel
        # absmax — the int8-KV first-prompt idiom, pointed at weights);
        # an already-quantized tree (e.g. activation-aware PTQ) rides
        # as-is and None adopts its mode. The mode is STRUCTURE of the
        # param tree, so every traced program keys on it for free and
        # kernel dispatch sees it via the weight_dtype meta key.
        params, self._wq = ensure_quantized(params, weight_quant)
        if "qkv_proj" in params.get("layers", {}):
            # one way in: the engine makes the leaf itself, per shard
            # over a mesh, where a column split of a [q | k | v] made
            # elsewhere hands shard 0 query heads only
            raise ValueError(
                "ServingEngine takes q_proj / k_proj / v_proj and fuses"
                " them itself (per shard over a mesh): this tree "
                "already holds the fused leaf 'qkv_proj'")
        self._mesh = normalize_mesh(mesh)
        if self._wq and self._mesh is not None and self._mesh.tp > 1:
            raise ValueError(
                f"ServingEngine(weight_quant={self._wq!r}) cannot shard"
                f" over tp={self._mesh.tp} > 1: packed-int4 rows and "
                "per-channel scale trees need per-shard packing specs "
                "(named headroom) — run quantized serving single-device"
                " or on tp=1 groups")
        if self._mesh is not None:
            ok, reason = self._mesh.supports(cfg)
            if not ok:
                # clean rejection, same reason-string contract as the
                # kernel registry's supports() predicates
                raise ValueError(f"ServingEngine(mesh=...): {reason}")
            params = self._mesh.shard(
                params, self._mesh.param_specs(cfg, params))
        # rebound, not only stored: over a mesh the three sharded
        # stacks are this constructor's own, and go before the pools
        # are made
        self.params = params = self._fuse_qkv(params, cfg)
        self.cfg = cfg
        # prefill-chunk kernel routing: False = always the verbatim
        # gather/cached_forward/scatter chunk;
        # "auto" (default, FLAGS_fused_prefill) = pool-direct fused
        # chunk where the registry supports BOTH prefill-block kernels,
        # the verbatim chunk elsewhere (bit-identical by construction);
        # "pallas"/"ref" force. Tensor-parallel engines (tp > 1) and
        # the "gather" placement keep the unfused chunk — gather's
        # bit-parity contract IS the single-device op sequence, and the
        # sharded prefill body is not fused yet.
        self._fused_prefill = _fused_prefill_mode(fused_prefill)
        self._prefill_mesh_ok = self._mesh is None or (
            self._mesh.tp == 1 and self._mesh.collective != "gather")
        if self._fused_prefill == "pallas" and not self._prefill_mesh_ok:
            # an explicit pin must never silently no-op (the PR-7
            # rms_norm precedent)
            raise ValueError(
                'fused_prefill="pallas" cannot be honored on this mesh'
                " — tensor-parallel (tp > 1) and gather-placement "
                "prefill run the unfused chunk by contract; use "
                'collective="psum" with tp=1 or drop the pin')
        # registry dispatch outcome captured when a fused prefill
        # program traces; None until then (see _make_prefill_fn_fused)
        self._prefill_variant = None
        # route actually built per (bucket, kernel-route) program-cache
        # key ("pallas" | "ref"), for the timeline's variant
        # attribution (tools/trace_summary.py) — keyed exactly like
        # _prefill_fns so a route change cannot stale the attribution
        self._prefill_kind: Dict[tuple, str] = {}
        # what the registry picked while the decode program traced
        # (see _make_decode_fn); None until the first trace
        self._decode_variant = None
        self.capacity = int(capacity)
        self.block_size = int(block_size)
        self.max_seq_len = int(max_seq_len
                               or cfg.max_position_embeddings)
        if self.max_seq_len > cfg.max_position_embeddings:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} exceeds the rope table "
                f"bound max_position_embeddings "
                f"= {cfg.max_position_embeddings}")
        self.buckets = tuple(sorted({int(b) for b in prefill_buckets}))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError("prefill_buckets must be positive")
        BS = self.block_size
        # the chunk's dense view is MB*BS wide; the last chunk may pad
        # past max_seq_len by up to a bucket, so the table gets the slack
        # (table width only — the physical pool is shared and unchanged)
        self.max_blocks = -(-(self.max_seq_len + self.buckets[-1]) // BS)
        if num_blocks is None:
            num_blocks = self.capacity * (-(-self.max_seq_len // BS)) + 1
        self.num_blocks = int(num_blocks)

        if cache_dtype in ("int8", jnp.int8):
            self._quant = True
        elif cache_dtype in (None, "bfloat16", "float32",
                             jnp.bfloat16, jnp.float32):
            self._quant = False
        else:
            raise ValueError(f"cache_dtype must be bfloat16|float32|int8,"
                             f" got {cache_dtype!r}")
        # the pools are as deep as the layers that hold keys and values
        L = getattr(cfg, "num_kv_layers", cfg.num_hidden_layers)
        KV, hd = cfg.num_key_value_heads, cfg.head_dim
        pool_dtype = jnp.int8 if self._quant else cfg.dtype
        shape = (L, self.num_blocks, BS, KV, hd)
        # pools shard their head-dim CONTENTS; page indices stay
        # host-global, so BlockManager/prefix-cache logic below is
        # identical with or without a mesh. A sharded pool is made in
        # place: whole on one device first, the two pools of a
        # deployment that needs the mesh would not fit beside the
        # weights
        where = (None if self._mesh is None
                 else self._mesh.sharding(self._mesh.pool_spec))
        self._k_pools = jnp.zeros(shape, pool_dtype, device=where)
        self._v_pools = jnp.zeros(shape, pool_dtype, device=where)
        self._kv_scales = None       # (k [L,KV], v [L,KV]) once calibrated

        # a model with window layers: a second class of pages under
        # the same manager, in a pool of its own that holds every
        # slot's whole ring (and the scratch page)
        self._ring = pat.ring(BS, self.buckets[-1]) \
            if pat is not None and pat.window else 0
        self.window_blocks = self.capacity * self._ring + 1 \
            if self._ring else 0
        self.mgr = BlockManager(
            self.num_blocks, BS, self.max_blocks,
            window=pat.window if self._ring else None,
            window_blocks=self.window_blocks, window_ring=self._ring)
        # reserve physical page 0 as scratch: padded table entries (and
        # inactive decode slots) default there, so their writes land in
        # a page no live sequence ever reads
        scratch = self.mgr.allocate(_SCRATCH_SEQ, 1)
        assert scratch == [0], "scratch must be page 0 (tables pad with 0)"

        self._pcache = None
        # host-RAM KV offload tier (prefix_cache.py): kv_offload=True
        # (or an int host-page budget) makes eviction SPILL refcount-1
        # radix pages to host memory instead of dropping them, and a
        # prefix hit on a spilled node restore them — effective
        # prefix-cache capacity becomes HBM + host RAM
        self._kv_offload = bool(kv_offload)
        self._offload_extract_fn = None
        self._offload_insert_fn = None
        # spill/restore move in fixed-width multi-page WINDOWS (one
        # jitted gather + one host transfer per window instead of a
        # program per page; padded index entries point at scratch page
        # 0 — the disagg handoff idiom)
        self._offload_window = max(1, int(os.environ.get(
            "PADDLE_TPU_OFFLOAD_WINDOW", "8")))
        # one physical page across BOTH pools, in bytes (the spill/
        # restore byte counters): over the layers that hold KV
        self._page_nbytes = int(2 * L * BS * KV * hd
                                * jnp.dtype(pool_dtype).itemsize)
        if kv_offload and not prefix_cache:
            raise ValueError(
                "kv_offload requires prefix_cache=True: the host tier "
                "spills radix-tree pages, not per-request tables")
        # a prefix match skips tokens; nobody stored the recurrent state
        # after them, and a window layer's pages behind the window are
        # gone, so for a pattern-run model the cache stays off and every
        # request it would have looked up is counted
        self._prefix_skipped = bool(prefix_cache) and pat is not None
        self._state = None
        # the decode program's forward, picked once: dense, the same
        # per-shard body under shard_map, or the hybrid model's (which
        # carries the slots' recurrent state)
        self._decode_forward = (self._dense_forward if self._mesh is None
                                else self._tp_forward)
        if pat is not None:
            from . import hybrid
            self._decode_forward = self._hybrid_forward
            prefix_cache = False
            self._state = hybrid.init_state(
                cfg, self.capacity, jnp.dtype(state_dtype or jnp.float32),
                self.window_blocks, BS, self._ring)
            self._state_reset_fn = jax.jit(hybrid.reset_slot,
                                           donate_argnums=(0,))
        if prefix_cache:
            from .prefix_cache import PrefixCache, make_page_copier
            self._copy_fn = make_page_copier()
            budget = (int(kv_offload)
                      if kv_offload and kv_offload is not True else None)
            self._pcache = PrefixCache(
                self.mgr, BS, copy_page=self._copy_page,
                spill_pages=self._spill_pages if kv_offload else None,
                restore_pages=(self._restore_pages if kv_offload
                               else None),
                host_budget_pages=budget)

        C, MB = self.capacity, self.max_blocks
        self._slots = [_Slot() for _ in range(C)]
        # SLO-aware admission (inference/admission.py): priority
        # classes with FIFO tie-break, per-request admission deadlines,
        # aging for starvation-freedom. Default submissions (one class,
        # no deadline, no aging) pop in exact FIFO order — the PR-1
        # contract unchanged.
        self._queue = AdmissionQueue(aging_s=aging_s,
                                     clock=self._clock)
        # per-class queue-wait running stats + SLO attainment counters,
        # updated O(1) at admit/expire so metrics() never scans the
        # request list per class: cls -> [admitted, wait_ms_sum,
        # wait_ms_max]; slo = [with-deadline seen, attained]
        self._sched_cls: Dict[int, List[float]] = {}
        self._slo = [0, 0]
        self._requests: List[Request] = []
        self._next_id = 0
        self._slot_tables = np.zeros((C, MB), np.int32)  # true tables
        # prefill WRITE tables: identical to the true tables except that
        # shared-prefix entries point at scratch page 0 — the prefill
        # scatter can then never write a page another request (or the
        # tree) reads, whatever the chunk computes
        self._slot_wtables = np.zeros((C, MB), np.int32)
        # decode-program inputs (host mirrors). Mid-prefill slots keep
        # table 0 / seq 0 here: their decode write must hit scratch, not
        # their half-written prompt pages.
        self._h_tok = np.zeros((C,), np.int32)
        self._h_seq = np.zeros((C,), np.int32)
        self._h_tables = np.zeros((C, MB), np.int32)
        self._h_temps = np.zeros((C,), np.float32)
        # a window layer's ring a slot (the rows of state["win_tables"]);
        # a row changes every block_size tokens of its slot, so it has a
        # dirty mark of its own and the decode inputs are not re-sent
        self._h_wtab = np.zeros((C, self._ring), np.int32)
        self._dirty_w = False
        self._dirty = True
        self._d_tok = self._d_seq = None
        self._d_tables = self._d_temps = None
        self._d_key = jax.random.key(seed)
        if self._mesh is not None:
            # donated carried state must live replicated ON the mesh:
            # donating a buffer the jit would first have to reshard
            # silently voids the donation (and warns) every step
            self._d_key = self._mesh.replicate(self._d_key)

        self._decode_fn = None
        # the registry of compiled programs (observability/programs.py)
        # captures a program at its first dispatch UNDER A PROFILER
        # SESSION: its keys there, and which programs it has
        self._program_keys = []
        self._decode_noted = False
        self._prefill_noted = set()     # keys of _prefill_fns
        self._decode_route = None
        self._prefill_fns: Dict[int, object] = {}
        self._calib_fn = None
        self._calib_bucket = None
        # *_traces counters increment inside the traced python bodies,
        # which only run when XLA (re)traces — they count compilations,
        # not calls. The tier-1 suite pins steady state to 1 decode
        # program + <=1 per prefill bucket over a 30-request stream.
        self.counters = {
            "decode_traces": 0, "prefill_traces": {},
            "calibration_traces": 0, "decode_steps": 0,
            "prefill_chunks": 0, "prefill_tokens": 0,
            # bucket-pad rows fed to prefill chunks (the compute the
            # RAGGED fused-prefill kernels skip; the unfused chunk
            # pays it — the serving_prefill bench's pad-FLOPs counter)
            "prefill_pad_tokens": 0,
            "live_slot_steps": 0,
            "tokens_generated": 0, "requests_submitted": 0,
            "requests_completed": 0, "drain_truncations": 0,
            "preemptions": 0, "requeues": 0, "deadline_expired": 0,
            # host-tier handoff pair: trace counter (spill extract +
            # restore insert, <=1 each — they trace lazily on the first
            # spill) and the bytes moved each direction
            "offload_traces": 0, "kv_spill_bytes": 0,
            "kv_restore_bytes": 0,
            # steps that ran a prefill chunk AND a decode step: what
            # stretches a token gap
            "mixed_steps": 0,
            # prompt tokens of fresh admissions the prefix cache was
            # asked about, and those it matched
            "prefix_lookup_tokens": 0, "prefix_hit_tokens": 0,
        }
        if pat is not None:
            # the expert_* three are summed on the device (the state's
            # "stats", carried by the decode program) and folded in
            # here only when metrics() reads them
            self.counters.update(dict.fromkeys(pat.counters, 0))
        self._t_first = None
        self._t_last = None
        self._metrics_reset_t = None   # TTFTs from before this are warmup
        self.last_drain_truncated = False
        # observability: None when disabled — every hook below is a
        # single `is not None` check, so the disabled hot loop allocates
        # NO event objects and issues NO extra device syncs (the per-
        # step d2h token read in _run_decode stays the only sync point).
        # telemetry implies observability: the plane's alerts land
        # timeline events and stall dumps, both owned by the harness.
        _tcfg = TelemetryConfig.coerce(telemetry)
        if observability or _tcfg is not None:
            self._obs = (observability
                         if isinstance(observability, Observability)
                         else Observability())
            self._obs.registry.adopt_counters(self.counters)
            if self._kv_offload:
                # handoff_ms-style distributions for the host tier
                self._obs.ensure_histograms(("spill_ms", "restore_ms"))
        else:
            self._obs = None
        # serving-collective instrumentation: a mesh'd engine with
        # observability on binds an engine-scoped flight recorder and
        # replays the DECLARED per-step collective inventory around
        # each dispatched program — host-observed spans (the engine's
        # one-sync-per-step philosophy), byte counters exact because
        # the shapes are static. metrics() surfaces them under
        # "collectives" exactly like Trainer.metrics().
        self._flight = None
        self._coll_decode = ()
        self._coll_prefill: Dict[int, tuple] = {}
        if self._mesh is not None and self._obs is not None:
            from ..distributed.flight_recorder import FlightRecorder
            rec = FlightRecorder(capacity=4096)
            rec.enabled = True
            self._flight = self._obs.bind_flight_recorder(rec)
            self._coll_decode = tuple(self._mesh.collective_inventory(
                cfg, B=self.capacity))
        # continuous telemetry plane (r22): samples this engine's
        # metrics() on a step cadence into bounded time-series with
        # burn-rate/anomaly alerting. None when disabled — the hot loop
        # pays one `is not None` check, nothing else.
        self._telemetry = None
        if _tcfg is not None:
            self._telemetry = TelemetryPlane(
                _tcfg, on_alert=self._telemetry_alert)
            self._telemetry.register("serving_engine", self.metrics,
                                     counters=self.counters)

    def _record_collectives(self, inventory):
        """Open one CommTask per declared collective class; returns the
        tasks for :meth:`_end_collectives` after the program's sync."""
        if self._flight is None or not inventory:
            return None
        return [self._flight.begin(op, ax, shape, dt)
                for op, ax, shape, dt in inventory]

    def _end_collectives(self, tasks):
        if tasks:
            for t in tasks:
                self._flight.end(t)

    def _upload(self, x):
        """Host mirror -> device, committed replicated on the mesh when
        tensor-parallel (so donated carried state never reshards)."""
        if self._mesh is not None:
            return self._mesh.replicate(np.ascontiguousarray(x))
        return jnp.asarray(x)

    def _copy_page(self, src: int, dst: int):
        """COW primitive for the prefix cache: device-copy one physical
        page in both pools (one jitted program, traced once — src/dst
        ride as int32 scalars)."""
        self._k_pools, self._v_pools = self._copy_fn(
            self._k_pools, self._v_pools, jnp.asarray(src, jnp.int32),
            jnp.asarray(dst, jnp.int32))

    # -- host-RAM KV offload tier -------------------------------------
    def _make_offload_fns(self):
        """The host-tier handoff pair — the PR-10 extract/device_put/
        insert machinery pointed inward, WINDOWED (r17): ``extract``
        gathers a fixed-width block of ``_offload_window`` physical
        pages from both pools in one program, ``insert`` scatters a
        restored window back (donated, so the pools update in place).
        Padded index entries point at scratch page 0 on both sides
        (the disagg fixed-width idiom), so one trace each covers every
        batch size, ever."""
        counters = self.counters

        def extract(kp, vp, idx):
            counters["offload_traces"] += 1
            return kp[:, idx], vp[:, idx]

        def insert(kp, vp, idx, kpag, vpag):
            counters["offload_traces"] += 1
            return (kp.at[:, idx].set(kpag), vp.at[:, idx].set(vpag))

        return (jax.jit(extract), jax.jit(insert, donate_argnums=(0, 1)))

    def _spill_pages(self, pages):
        """PrefixCache batch-spill callback: the pages' raw bytes ->
        host memory in fixed-width windows — ONE jitted gather + ONE
        host transfer per pool per window replaces the per-page
        programs. The window leaves the device through ``host_put``
        (pinned host memory where the backend offers it — the fast d2h
        path the per-page tier used), then splits into per-page numpy
        payloads so the host tier's per-page budget accounting stays
        exact; only :meth:`_restore_pages` reads them."""
        from .prefix_cache import host_put
        if self._offload_extract_fn is None:
            (self._offload_extract_fn,
             self._offload_insert_fn) = self._make_offload_fns()
        W = self._offload_window
        t0 = self._clock()
        payloads = []
        for w0 in range(0, len(pages), W):
            win = list(pages[w0:w0 + W])
            idx = np.zeros((W,), np.int32)
            idx[:len(win)] = win
            kw, vw = self._offload_extract_fn(
                self._k_pools, self._v_pools, jnp.asarray(idx))
            kw, vw = host_put(kw), host_put(vw)   # pinned d2h per pool
            kw_np, vw_np = np.asarray(kw), np.asarray(vw)
            for j in range(len(win)):
                payloads.append((np.ascontiguousarray(kw_np[:, j]),
                                 np.ascontiguousarray(vw_np[:, j])))
        self.counters["kv_spill_bytes"] += self._page_nbytes * len(pages)
        if self._obs is not None and pages:
            dur = (self._clock() - t0) * 1e3
            per = dur / len(pages)
            for _ in pages:      # one observation per PAGE (the
                self._obs.hist("spill_ms").observe(per)   # count
            self._obs.timeline.record(   # contract: count == pages)
                "kv_spill", pages=[int(p) for p in pages],
                bytes=self._page_nbytes * len(pages),
                dur_ms=round(dur, 3))
        return payloads

    def _restore_pages(self, payloads, dsts):
        """PrefixCache batch-restore callback: device_put the spilled
        windows back and scatter them into the destination pages with
        the donated window insert — byte-identical to what was
        spilled. The insert is DISPATCHED, never synced: the
        device-side copy overlaps the suffix prefill chunk the caller
        issues next (which consumes the updated pools) instead of
        completing before it."""
        if self._offload_insert_fn is None:
            (self._offload_extract_fn,
             self._offload_insert_fn) = self._make_offload_fns()
        W = self._offload_window
        ps = self._k_pools.shape           # [L, N, BS, KV, hd]
        t0 = self._clock()
        for w0 in range(0, len(dsts), W):
            win_p = payloads[w0:w0 + W]
            win_d = list(dsts[w0:w0 + W])
            idx = np.zeros((W,), np.int32)
            idx[:len(win_d)] = win_d
            kw = np.zeros((ps[0], W) + ps[2:], self._k_pools.dtype)
            vw = np.zeros_like(kw)
            for j, (kpg, vpg) in enumerate(win_p):
                kw[:, j] = kpg
                vw[:, j] = vpg
            if self._mesh is not None:
                kw = self._mesh.replicate(kw)
                vw = self._mesh.replicate(vw)
            else:
                dev = next(iter(self._k_pools.devices()))
                kw = jax.device_put(kw, dev)
                vw = jax.device_put(vw, dev)
            self._k_pools, self._v_pools = self._offload_insert_fn(
                self._k_pools, self._v_pools, jnp.asarray(idx), kw, vw)
        self.counters["kv_restore_bytes"] += \
            self._page_nbytes * len(dsts)
        if self._obs is not None and dsts:
            dur = (self._clock() - t0) * 1e3
            per = dur / len(dsts)
            for _ in dsts:
                self._obs.hist("restore_ms").observe(per)
            self._obs.timeline.record(
                "kv_restore", pages=[int(d) for d in dsts],
                bytes=self._page_nbytes * len(dsts),
                dur_ms=round(dur, 3))

    # -- public API ---------------------------------------------------
    def _alloc_tokens(self, req: Request) -> int:
        """Token span this engine allocates KV pages for. The colocated
        engine holds the whole request (prompt + generation); the
        disaggregated prefill worker overrides to prompt-only — its
        pages hand off to the decode group before generation."""
        return int(req.prompt.size) + int(req.gen.max_new_tokens)

    def submit(self, prompt, gen: Optional[GenerationConfig] = None,
               priority: Optional[int] = None,
               deadline_s: Optional[float] = None) -> Request:
        """Enqueue one request. Admission happens inside ``step()``
        when a slot and enough KV pages are free, ordered by priority
        class (LOWER = more urgent; FIFO within a class, aging per the
        engine's ``aging_s``). ``deadline_s`` bounds queue wait: a
        request still queued past its deadline is rejected (marked
        ``expired``), never admitted late. ``priority``/``deadline_s``
        default from ``gen``."""
        gen = gen or GenerationConfig()
        if gen.top_k > 0 or gen.top_p < 1.0:
            raise NotImplementedError(
                "ServingEngine: per-request top-k/top-p would bake the "
                "knob values into the traced decode program (a retrace "
                "per distinct config); greedy/temperature ride as traced"
                " arrays. Use generate()/generate_paged for top-k/top-p")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        total = int(prompt.size) + int(gen.max_new_tokens)
        if total > self.max_seq_len:
            raise ValueError(
                f"prompt+max_new_tokens = {total} exceeds engine "
                f"max_seq_len = {self.max_seq_len}")
        if priority is None:
            priority = getattr(gen, "priority", 1)
        if deadline_s is None:
            deadline_s = getattr(gen, "deadline_s", None)
        req = Request(self._next_id, prompt, gen,
                      submit_t=self._clock(),
                      priority=int(priority), deadline_s=deadline_s)
        need = -(-self._alloc_tokens(req) // self.block_size)
        if need > self.num_blocks - 1:          # minus the scratch page
            raise ValueError(
                f"request needs {need} KV pages but the pool only has "
                f"{self.num_blocks - 1}; raise num_blocks")
        self._next_id += 1
        req.qentry = self._queue.push(req, cls=req.priority,
                                      submit_t=req.submit_t,
                                      deadline_s=deadline_s)
        self._requests.append(req)
        self.counters["requests_submitted"] += 1
        if self._obs is not None:
            self._obs.timeline.record(
                "submit", req.req_id, prompt_tokens=int(prompt.size),
                max_new_tokens=int(gen.max_new_tokens),
                priority=req.priority,
                **({"deadline_s": deadline_s}
                   if deadline_s is not None else {}))
        return req

    def step(self) -> bool:
        """One scheduler iteration: admit from the queue, run one
        prefill chunk (if an admission is in flight), then one decode
        step over all live slots. Returns True if any work ran —
        including deadline expiries, which shrink the queue and so
        count as scheduler progress (a drain() whose last step only
        expires a request must finish cleanly, not report starvation)."""
        obs = self._obs
        with span("serve/step", obs, hist="step_ms") as whole:
            if self._t_first is None:
                self._t_first = self._clock()
            with span("serve/admit", obs):
                expired = self._admit()
            chunk = self._run_prefill()
            did = self._run_decode()
            if chunk and did:
                self.counters["mixed_steps"] += 1
            did = did or chunk
            if did:
                self._t_last = self._clock()
            else:
                whole.drop()      # an idle poll is no step of the window
            if obs is not None or self._check_inv:   # telemetry has obs
                with span("serve/observe", obs):
                    self._observe()
        if whole.dur_ms is not None \
                and obs.step_deadline_s is not None \
                and whole.dur_ms > obs.step_deadline_s * 1e3:
            obs.stall_dump(
                f"step took {whole.dur_ms:.1f} ms "
                f"(deadline {obs.step_deadline_s * 1e3:.1f} ms)",
                self.scheduler_snapshot())
        return did or expired > 0

    def _observe(self):
        """What a step pays for being watched: gauges and the retrace
        watchdog (pure host bookkeeping — host mirrors only, never the
        device), the telemetry plane's sample, the invariant check."""
        obs = self._obs
        if obs is not None:
            free = len(self.mgr.free)
            vals = {
                "pages_free": free,
                "pages_in_use": self.num_blocks - free,
                "kv_refcount_total": int(self.mgr.refcount.sum()),
                "queue_depth": len(self._queue),
                "live_slots": sum(1 for s in self._slots
                                  if s.phase != "idle"),
            }
            if self._slo[0]:
                vals["slo_attainment"] = self._slo[1] / self._slo[0]
            if self._pcache is not None:
                st = self._pcache.stats
                looked = st["hits"] + st["misses"]
                vals["prefix_tree_pages"] = self._pcache.cached_pages
                vals["prefix_hit_ratio"] = (round(st["hits"] / looked, 4)
                                            if looked else 0.0)
                if self._kv_offload:
                    vals["prefix_host_pages"] = self._pcache.host_pages
            obs.sample_gauges(self._clock(), vals)
            if obs.watchdog.check(self.counters):
                obs.timeline.record("retrace",
                                    events=len(obs.watchdog.events))
        if self._telemetry is not None:
            self._telemetry.on_step()
        if self._check_inv:
            # PADDLE_TPU_CHECK_INVARIANTS=1: assert the lifecycle
            # checker's manager+cache invariant set after every step
            self.mgr.check()
            if self._pcache is not None:
                self._pcache.check()

    def _fuse_qkv(self, params, cfg):
        """The dense decoder's tree as this engine's programs read it:
        the three projection stacks as the ONE leaf ``qkv_proj``
        (``fused_decode_block.fuse_qkv``), which the layer loop's
        product reads in place, where it copies a layer of each of the
        three out and re-lays it out first (``qkv_project``). Made once,
        here, before the pools exist; over a mesh per shard, so a shard
        holds [q | k | v] of its own heads. Nothing is donated: on one
        device the three stacks are the CALLER's arrays, and stay alive
        beside the leaf for as long as the caller keeps them (through
        this constructor at the least: its peak is one leaf higher),
        as are a mesh's when the tree arrives already placed; copies
        that ``ServingMesh.shard`` had to make are this constructor's
        own, and are freed before the pools are made. A quantized tree (leaves
        with scales and packed rows) keeps its leaves, and a pattern-run
        model's tree has no such stacks (inference/hybrid.py runs its
        own loop)."""
        from ..ops.pallas.fused_decode_block import QKV_LEAVES, fuse_qkv
        layers = params.get("layers", {})
        if self._wq or not all(k in layers for k in QKV_LEAVES):
            return params
        wrap = jax.jit
        if self._mesh is not None:
            mesh = self._mesh.mesh
            col = self._mesh.param_specs(cfg)["layers"]["q_proj"]
            wrap = lambda f: jax.jit(                        # noqa: E731
                shard_map_norep(f, mesh, (col,) * 3, col))
        fused = fuse_qkv(layers, wrap)
        if self._mesh is not None:
            # the leaf is written before the shards' three stacks lose
            # their last reference (``__init__`` rebinds ``params``):
            # the allocator then never holds them beside the pools
            jax.block_until_ready(fused["qkv_proj"])
        return {**params, "layers": fused}

    @property
    def decode_variant(self) -> Dict:
        """Which launches this engine's decode program holds:
        ``{"attn": ..., "mlp": ..., "operands": {...}, "qkv": ...}`` —
        the variant
        of ``paged_attention_decode`` ("pallas" | "xla") and of
        ``decode_mlp_block`` ("pallas_fused" | "unfused": also where the
        program has no such stage, the "gather" placement and a model
        with expert layers) that the kernel registry picked, and for
        each Pallas launch of the layer loop how it takes its layer of
        the KV pools / stacked weights (``fused_decode_block
        .launch_operands``); ``"qkv"`` is the form of the q/k/v
        projections in the tree the program traced over
        ("fused_stack": one product over ``qkv_proj``, read in place;
        "per_leaf": three). It IS the registry's record of the
        dispatches made while the decode program traced, so later env
        changes — the VMEM budget, a ``KERNELS.force`` pin around a
        ``metrics()`` call — cannot make the report drift from the
        compiled program. Before the first decode step there is no
        program, and the names are None."""
        if self._decode_variant is not None:
            return dict(self._decode_variant)
        return {"attn": None, "mlp": None, "operands": {}, "qkv": None}

    @property
    def weight_quant_variant(self) -> Dict:
        """Which weight-dtype class the engine's programs run:
        ``{"mode": "off"}`` for plain fp weights, else ``{"mode":
        "int8"|"int4", "weight_dtype": ..., "attn": ..., "mlp": ...}``
        with the decode variants that serve the quantized tree (of
        :attr:`decode_variant`, the trace-time record)."""
        if not self._wq:
            return {"mode": "off"}
        v = self.decode_variant
        return {"mode": self._wq, "weight_dtype": self._wq,
                "attn": v["attn"], "mlp": v["mlp"]}

    def _active_arm(self) -> str:
        """Which roofline arm the live decode step runs: both launches
        Pallas kernels, or the reference compositions."""
        v = self.decode_variant
        return "pallas_fused" if (v["attn"], v["mlp"]) == (
            "pallas", "pallas_fused") else "unfused"

    def _roofline_metrics(self) -> Dict:
        """Per-decode-variant modeled HBM bytes/step + the
        bandwidth-bound step-time floor (``observability/roofline``'s
        closed-form arm model × layers + the lm-head read), with the
        achieved-bandwidth fraction filled for the ACTIVE arm when a
        measured ``decode_step_ms`` distribution exists. Pure host
        arithmetic on the engine's static dims, computed on demand —
        the disabled-observability hot path still allocates nothing."""
        import jax.numpy as jnp

        from ..observability.roofline import (decode_roofline,
                                              decode_step_bytes)

        cfg = self.cfg
        if self._pattern is not None:
            # the arm model is a dense decoder's (attention + one MLP a
            # layer): it does not reckon expert, recurrent or window
            # layers
            return {"active": "unfused", "layers": cfg.num_hidden_layers,
                    "reckoned": False,
                    "why": "decode_step_bytes models dense-MLP layers "
                           "with KV; this model has expert layers, "
                           f"{self._pattern.recurrent_layers} recurrent "
                           f"and {self._pattern.window_layers} window "
                           "layers (see metrics()['pattern'])"}
        tp = 1 if self._mesh is None else self._mesh.tp
        act = jnp.dtype(cfg.dtype).itemsize
        pool = jnp.dtype(self._k_pools.dtype).itemsize
        wbytes = {"int8": 1.0, "int4": 0.5}.get(self._wq or "",
                                                float(act))
        # every layer of a dense decoder holds KV; a pattern's other
        # layers are not this model's to reckon
        L = getattr(cfg, "num_kv_layers", cfg.num_hidden_layers)
        per_layer = decode_step_bytes(
            self.capacity, cfg.hidden_size,
            cfg.num_attention_heads // tp,
            cfg.num_key_value_heads // tp, cfg.head_dim,
            cfg.intermediate_size // tp, self.block_size,
            self.max_blocks, act_itemsize=act, weight_itemsize=wbytes,
            pool_itemsize=pool)
        head = cfg.vocab_size * cfg.hidden_size * act
        step_bytes = {k: int(v * L + head)
                      for k, v in per_layer.items()}
        active = self._active_arm()
        measured = {}
        if self._obs is not None:
            snap = self._obs.registry.histogram(
                "decode_step_ms").snapshot()
            if snap["count"]:
                measured[active] = snap["mean"] * 1e3
        r = decode_roofline(step_bytes, measured_us=measured)
        r["active"] = active
        r["layers"] = L
        return r

    @property
    def idle(self) -> bool:
        return not self._queue and all(
            s.phase == "idle" for s in self._slots)

    # -- fleet-router surface (inference/fleet.py) --------------------
    @property
    def queue_depth(self) -> int:
        """Requests submitted but not yet admitted — the router's
        admission-backpressure signal."""
        return len(self._queue)

    @property
    def live_slots(self) -> int:
        return sum(1 for s in self._slots if s.phase != "idle")

    @property
    def prefix_cache_version(self) -> int:
        """Monotone radix-tree version (0 without a prefix cache) —
        the router refreshes its cached tree summary when this moves."""
        return 0 if self._pcache is None else self._pcache.version

    def prefix_summary(self) -> Dict[int, int]:
        """The router's tree summary: ``{prefix_hash: n_tokens}`` for
        every page-aligned cached path (empty without a prefix
        cache)."""
        return {} if self._pcache is None else self._pcache.summary()

    def drain(self, max_steps: Optional[int] = None) -> int:
        """Step until queue and slots are empty; returns step count.

        Hitting ``max_steps`` with work still pending is recorded —
        ``last_drain_truncated`` is set and the ``drain_truncations``
        counter increments — so a capped drain is distinguishable from
        a clean one at the call site. Starvation (a step that can run
        nothing while requests are queued) raises, after writing a
        flight-recorder stall dump when observability is on."""
        return _drain_loop(
            self, max_steps,
            starve_reason="drain starved: queued requests cannot be "
                          "admitted",
            starve_error="engine starved: queued requests cannot be "
                         "admitted (KV pool too small for the "
                         "in-flight mix?)")

    def _drain_truncated_event(self, n: int):
        if self._obs is not None:
            self._obs.timeline.record(
                "drain_truncated", steps=n,
                queue_depth=len(self._queue),
                live_slots=sum(1 for s in self._slots
                               if s.phase != "idle"))

    def scheduler_snapshot(self) -> Dict:
        """Host-side scheduler state for stall dumps: queue depth, slot
        phases, per-slot seq_len, free pages, prefix-cache state."""
        snap = {
            "queue_depth": len(self._queue),
            "queued": [{"req_id": e.item.req_id,
                        "prompt_tokens": int(e.item.prompt.size),
                        "priority": e.item.priority,
                        "requeues": e.requeues,
                        "need_pages":
                            -(-self._alloc_tokens(e.item)
                              // self.block_size)}
                       for e in list(self._queue)[:16]],
            "slots": [{"slot": i, "phase": s.phase,
                       "req_id": s.req.req_id if s.req else None,
                       "seq_len": s.seq_len,
                       "prefill_pos": s.prefill_pos}
                      for i, s in enumerate(self._slots)],
            "pages_free": len(self.mgr.free),
            "num_blocks": self.num_blocks,
            "capacity": self.capacity,
        }
        if self._pcache is not None:
            snap["prefix_cache"] = self._pcache.metrics()
        return snap

    @property
    def telemetry(self) -> Optional[TelemetryPlane]:
        """The continuous telemetry plane, or None when disabled."""
        return self._telemetry

    def _telemetry_alert(self, alert: Dict):
        """Plane alert callback: stamp an ``alert`` timeline event; a
        page-severity alert additionally self-documents through the
        flight-recorder stall-dump machinery (scheduler snapshot + the
        alert that fired)."""
        obs = self._obs
        if obs is None:
            return
        obs.timeline.record(
            "alert", rule=alert.get("rule"),
            severity=alert.get("severity"), metric=alert.get("metric"),
            value=alert.get("value"), threshold=alert.get("threshold"))
        if (alert.get("severity") == "page"
                and self._telemetry is not None
                and self._telemetry.config.page_dumps):
            obs.stall_dump(
                f"telemetry alert: {alert.get('rule')} on "
                f"{alert.get('metric')}", self.scheduler_snapshot(),
                metrics={"alert": alert})

    def metrics(self) -> Dict:
        # the flight recorder parks raw collective_calls/bytes counters
        # in the adopted dict; they surface ONLY under the structured
        # "collectives" key below (the Trainer.metrics contract).
        # mixed_steps is read from ``counters`` itself (the benchmark's
        # mixed_step_pct.*): the key set of metrics() is frozen
        pat = self._pattern
        if pat is not None:
            self._fold_expert_stats()
        c = {k: (dict(v) if isinstance(v, dict) else v)
             for k, v in self.counters.items()
             if k not in self._COUNTERS_ONLY}
        if pat is not None:
            # one report; a model with recurrent layers keeps its older
            # name for it too
            c["pattern"] = self._pattern_metrics()
            if pat.recurrent_layers:
                c["recurrent"] = c["pattern"]
        if self._mesh is not None:
            c["mesh"] = self._mesh.describe()
        wall = ((self._t_last - self._t_first)
                if self._t_first is not None and self._t_last is not None
                else 0.0)
        c["wall_time_s"] = round(wall, 6)
        c["tokens_per_sec"] = (round(c["tokens_generated"] / wall, 3)
                               if wall > 0 else 0.0)
        # prompt tokens processed over the same window: prefill- vs
        # decode-bound workloads are indistinguishable without it
        c["prefill_tokens_per_sec"] = (
            round(c["prefill_tokens"] / wall, 3) if wall > 0 else 0.0)
        # TTFTs measured before the last reset_metrics() belong to the
        # warmup window — a request in flight across the reset keeps
        # its Request object but must not pollute this window's stats
        cut = self._metrics_reset_t
        ttfts = [r.ttft for r in self._requests
                 if r.ttft is not None
                 and (cut is None or (r.first_token_t or 0.0) >= cut)]
        c["ttft_ms_mean"] = (round(float(np.mean(ttfts)) * 1e3, 3)
                             if ttfts else None)
        c["ttft_ms_max"] = (round(float(np.max(ttfts)) * 1e3, 3)
                            if ttfts else None)
        steps = c["decode_steps"]
        c["slot_utilization"] = (
            round(c["live_slot_steps"] / (steps * self.capacity), 4)
            if steps else 0.0)
        c["decode_variant"] = self.decode_variant
        c["prefill_variant"] = self.prefill_variant
        c["weight_quant_variant"] = self.weight_quant_variant
        c["roofline"] = self._roofline_metrics()
        c["scheduler"] = self._scheduler_metrics()
        if self._pcache is not None:
            c["prefix_cache"] = self._pcache.metrics()
        if self._telemetry is not None:
            c["telemetry"] = self._telemetry.snapshot()
        if self._obs is not None:
            obs = self._obs
            c["latency"] = obs.latency_snapshot()
            c["gauges"] = obs.gauges_snapshot()
            c["retrace_warnings"] = len(obs.watchdog.events)
            c["stall_dumps"] = (len(obs.stall_dumps)
                                + obs.stall_dumps_suppressed)
            c["timeline_events"] = len(obs.timeline)
            c["timeline_dropped"] = obs.timeline.dropped
            if self._flight is not None:
                # the bound recorder feeds per-(op, axis) latency
                # histograms + call/byte counters — one structured
                # sub-dict, schema-frozen in test_observability
                c["collectives"] = _collectives_snapshot(self.counters,
                                                         obs)
        return c

    # counters that are read from ``counters`` itself (the benchmark's
    # counter ratios) and stay out of metrics(), whose key set is frozen
    _COUNTERS_ONLY = frozenset((
        "collective_calls", "collective_bytes", "mixed_steps",
        "prefix_lookup_tokens", "prefix_hit_tokens", "state_resets",
        "prefix_skipped_recurrent", "prefix_skipped_window",
        "expert_assignments", "expert_assignments_held",
        "expert_load_max", "experts_touched_held", "expert_layer_steps",
        "window_pages_released",
        "kv_tokens_held_window", "kv_tokens_seen_window",
        "kv_pages_live_global"))

    def _fold_expert_stats(self):
        """The routing counts the decode program summed on the device
        since the last reset, into ``counters`` (one small read)."""
        stats = np.asarray(self._state["stats"])
        for k, v in zip(("expert_assignments", "expert_assignments_held",
                         "expert_load_max", "experts_touched_held",
                         "expert_layer_steps"), stats):
            self.counters[k] = int(v)

    def _pattern_metrics(self) -> Dict:
        """What a pattern-run model adds to ``metrics()``: the pools
        the engine holds beside the global KV pools (a recurrent state
        a slot, the window layers' pages), what the window gave back,
        and the expert layer's routing over the decode steps since the
        reset (``load_skew``: the largest number of tokens one expert
        got in a step, over the mean an expert got)."""
        c, cfg, pat = self.counters, self.cfg, self._pattern
        layers = pat.expert_layers
        mean = (c["expert_assignments"]
                / (cfg.num_experts * layers * c["decode_steps"])
                if c["decode_steps"] else 0.0)
        out = {
            "recurrent_layers": pat.recurrent_layers,
            "kv_layers": cfg.num_kv_layers,
            "window_layers": pat.window_layers,
            pat.prefix_skip_counter: c[pat.prefix_skip_counter],
            "experts": {
                "held": cfg.num_local_experts, "of": cfg.num_experts,
                "offset": cfg.expert_offset,
                "assignments": c["expert_assignments"],
                "assignments_held": c["expert_assignments_held"],
                "held_share": (round(c["expert_assignments_held"]
                                     / c["expert_assignments"], 4)
                               if c["expert_assignments"] else None),
                "load_max": c["expert_load_max"],
                "load_skew": (round(c["expert_load_max"] / mean, 3)
                              if mean else None),
                # held experts that got a token, a layer a decode step
                "touched_held": (round(c["experts_touched_held"]
                                       / c["expert_layer_steps"], 3)
                                 if c["expert_layer_steps"] else None)}}
        if pat.recurrent_layers:
            out.update(
                state_bytes=sum(int(self._state[k].nbytes)
                                for k in ("ssm", "conv")),
                state_dtype=str(self._state["ssm"].dtype),
                state_resets=c["state_resets"])
        if pat.window:
            seen = c["kv_tokens_seen_window"]
            out["window"] = {
                "positions": pat.window, "ring_pages": self._ring,
                "pool_pages": self.window_blocks,
                "pool_bytes": sum(int(self._state[k].nbytes)
                                  for k in ("k_win", "v_win")),
                "pages_released": c["window_pages_released"],
                "held_share": (round(c["kv_tokens_held_window"] / seen, 4)
                               if seen else None)}
        return out

    def _scheduler_metrics(self) -> Dict:
        """The SLO-admission window report: per-class queue-wait stats
        (running O(1) sums — never a request-list scan), deadline
        attainment (fraction of deadline-carrying requests admitted
        within their deadline; None when none carried one), and the
        live queue depth. Same shape in both observability modes."""
        per = {str(cls): {
                   "admitted": int(st[0]),
                   "queue_wait_ms_mean": (round(st[1] / st[0], 3)
                                          if st[0] else 0.0),
                   "queue_wait_ms_max": round(st[2], 3)}
               for cls, st in sorted(self._sched_cls.items())}
        n, ok = self._slo
        return {"per_class": per,
                "slo_attainment": (round(ok / n, 4) if n else None),
                # the raw attainment counters: the telemetry plane's
                # burn-rate windows difference these across samples
                "slo_seen": int(n), "slo_attained": int(ok),
                "queue_depth": len(self._queue)}

    def reset_metrics(self):
        """Zero the throughput counters/timers (e.g. after a compile
        warmup pass). Trace counters are cumulative and stay — but the
        retrace watchdog arms HERE: any program that traces after this
        call is a steady-state retrace and warns."""
        for k in ("decode_steps", "prefill_chunks", "prefill_tokens",
                  "prefill_pad_tokens",
                  "live_slot_steps", "tokens_generated",
                  "requests_submitted", "requests_completed",
                  "drain_truncations", "preemptions", "requeues",
                  "deadline_expired", "kv_spill_bytes",
                  "kv_restore_bytes", "mixed_steps",
                  "prefix_lookup_tokens", "prefix_hit_tokens"):
            self.counters[k] = 0
        if self._pattern is not None:
            self.counters.update(dict.fromkeys(self._pattern.counters, 0))
            self._state = {**self._state, "stats": jnp.zeros_like(
                self._state["stats"])}
        self._sched_cls = {}
        self._slo = [0, 0]
        if self._pcache is not None:
            # workload counters like the above (the cached PAGES stay —
            # only the counts restart, so a warmed-up bench window
            # reports its own hits/skips, not the warmup's)
            for k in self._pcache.stats:
                self._pcache.stats[k] = 0
        self._t_first = self._t_last = None
        self._metrics_reset_t = self._clock()
        self._requests = [r for r in self._requests if not r.done]
        if self._flight is not None:
            # the recorder's call/byte counters live in the adopted
            # dict; reset_window() below restarts the collective
            # latency HISTOGRAMS, so the counters must restart with
            # them — metrics()["collectives"] reports ONE window
            # (calls == histogram count), never warmup-inflated totals
            self.counters.pop("collective_calls", None)
            self.counters.pop("collective_bytes", None)
        if self._obs is not None:
            self._obs.reset_window()
            self._obs.watchdog.mark_warmup(self.counters)

    # -- observability export -----------------------------------------
    @property
    def observability(self) -> Optional[Observability]:
        return self._obs

    def _require_obs(self) -> Observability:
        if self._obs is None:
            raise RuntimeError(
                "observability is disabled for this engine; construct "
                "with ServingEngine(..., observability=True)")
        return self._obs

    def export_trace(self, path: str) -> str:
        """Write the request-lifecycle chrome trace (+ gauge counter
        tracks + the per-arm roofline annotation track) to ``path`` —
        open in Perfetto / chrome://tracing."""
        from ..observability.roofline import roofline_chrome_events
        return self._require_obs().export_chrome(
            path,
            extra_events=roofline_chrome_events(self._roofline_metrics()))

    def write_timeline(self, path: str) -> str:
        """Write the structured per-phase JSONL (events + per-request
        records) to ``path`` — input for tools/trace_summary.py. The
        meta header carries the per-arm roofline model so the summary
        can print measured step time against the bandwidth floor."""
        return self._require_obs().write_jsonl(
            path, header={"capacity": self.capacity,
                          "num_blocks": self.num_blocks,
                          "block_size": self.block_size,
                          "roofline": self._roofline_metrics()})

    # -- scheduling ---------------------------------------------------
    def _temp_of(self, gen: GenerationConfig) -> float:
        return 0.0 if (gen.greedy or gen.temperature == 0.0) \
            else float(gen.temperature)

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _admit(self) -> int:
        """Admit from the queue until blocked; returns the number of
        deadline expiries (scheduler progress the caller must count)."""
        now = self._clock()
        expired = self._queue.pop_expired(now)
        for entry in expired:
            self._expire(entry.item, now)
        while self._queue:
            entry = self._queue.best(now)
            req = entry.item
            # a slot first — idle, or a strictly lower-priority decode
            # victim (candidate only; the preemption itself waits until
            # the page check passes). Slots are checked BEFORE pages so
            # a saturated engine never pays the prefix-cache acquire
            # (which pins pages and may device-copy a COW fork) on
            # every step just to release it again.
            slot_id = next((i for i, s in enumerate(self._slots)
                            if s.phase == "idle"), None)
            victim = None
            if slot_id is None:
                victim = self._preempt_candidate(req)
                if victim is None:
                    break
            acquired = None
            if req.resume is None:
                ok, acquired = self._acquire_pages(req)
                if not ok:
                    # the line head is page-starved. Fresh requests may
                    # not overtake it (page fairness — FIFO-within-
                    # order backpressure), but a RESUME entry allocates
                    # NOTHING and holds pages whose release is the only
                    # way the head ever unblocks, so the best resume
                    # entry admits instead (deadlock freedom: a
                    # preempted victim parked behind a page-short head
                    # must not pin the pool forever).
                    entry = self._queue.best(
                        now, pred=lambda e: e.item.resume is not None)
                    if entry is None:
                        break
                    req = entry.item
                    if slot_id is None:
                        # preemption rights are per-entry (raw class):
                        # re-pick the victim for the resume entry
                        victim = self._preempt_candidate(req)
                        if victim is None:
                            break
            if slot_id is None:
                slot_id = self._preempt(victim)
            self._queue.remove(entry)
            if req.resume is not None:
                # valid KV pages already attached (a preempted decode
                # slot, or a disaggregated KV handoff): re-enter decode
                # directly — no pages to allocate, no prefill
                self._admit_resume(slot_id, req, now)
                continue
            slot = self._slots[slot_id]
            if self._quant and self._kv_scales is None:
                # static scales calibrate from the first admitted prompt
                # BEFORE any prefill/decode program exists, so the
                # programs close over the final scale arrays
                self._calibrate(req.prompt)
            matched = shared = 0
            if acquired is not None:
                pages, matched, shared = acquired
                # matched pages join the block table directly; their
                # references transfer to this request's table entries
                self.mgr.attach(req.req_id, pages, owned=True)
                self.counters["prefix_lookup_tokens"] += int(
                    req.prompt.size)
                self.counters["prefix_hit_tokens"] += int(matched)
            pat = self._pattern
            if self._prefix_skipped:
                self.counters[pat.prefix_skip_counter] += 1
            if pat is not None and pat.recurrent_layers:
                # the slot's last request left its state there
                with span("serve/state_reset", self._obs, slot=slot_id):
                    self._state = self._state_reset_fn(
                        self._state, jnp.asarray(slot_id, jnp.int32))
                self.counters["state_resets"] += 1
            table = self.mgr.allocate(req.req_id,
                                      self._alloc_tokens(req))
            if self.mgr.window is not None:
                # the window class hands out pages as the request
                # reaches them; admission sets its worst case aside
                self.mgr.window.reserve(req.req_id,
                                        self._alloc_tokens(req))
            slot.req = req
            slot.phase = "prefill"
            slot.seq_len = 0
            slot.prefill_pos = matched     # prefill only the suffix
            self._slot_tables[slot_id] = 0
            self._slot_tables[slot_id, :len(table)] = table
            self._slot_wtables[slot_id] = self._slot_tables[slot_id]
            self._slot_wtables[slot_id, :shared] = 0
            self._record_admit(req, slot_id, now, matched)
        return len(expired)

    def _acquire_pages(self, req: Request):
        """Page-availability check for a fresh admission: ``(ok,
        acquired)``. Without a prefix cache this is a pure free-list
        check; with one, ``acquire()`` longest-prefix matches (capped
        at S-1 so the request always prefills >= 1 token, the logits
        source for its first sampled token), PINS the matched pages,
        and owns the backpressure check — free plus evictable must
        cover the un-matched remainder."""
        need = -(-self._alloc_tokens(req) // self.block_size)
        if self._pcache is None:
            win = self.mgr.window
            return (len(self.mgr.free) >= need and (
                win is None
                or win.can_reserve(self._alloc_tokens(req)))), None
        acquired = self._pcache.acquire(
            req.prompt, int(req.prompt.size) - 1, need)
        return acquired is not None, acquired

    def _record_admit(self, req: Request, slot_id: int, now: float,
                      matched: int = 0):
        """Admission bookkeeping shared by the fresh and resume paths:
        queue-wait stats per priority class, SLO attainment, the
        queue_wait histogram and the timeline event."""
        first = req.admit_t is None
        if first:
            # admit_t is the FIRST admission (queue-wait semantics);
            # a resume keeps it so per-request records report the
            # original admission wait, not the requeue wait
            req.admit_t = self._clock()
            wait_ms = (req.admit_t - req.submit_t) * 1e3
            st = self._sched_cls.setdefault(req.priority, [0, 0.0, 0.0])
            st[0] += 1
            st[1] += wait_ms
            st[2] = max(st[2], wait_ms)
            if req.deadline_s is not None:
                self._slo[0] += 1
                if wait_ms <= req.deadline_s * 1e3:
                    self._slo[1] += 1
            if self._obs is not None:
                self._obs.hist("queue_wait_ms").observe(wait_ms)
        if self._obs is not None:
            wait_ms = (self._clock() - req.submit_t) * 1e3
            self._obs.timeline.record(
                "admit" if first else "resume", req.req_id,
                slot=slot_id, queue_wait_ms=round(wait_ms, 3),
                matched_tokens=matched, priority=req.priority)

    def _expire(self, req: Request, now: float):
        """Admission deadline passed while queued: reject, never admit
        late. A fresh request holds no pages; an expired RESUME entry
        cannot occur (started entries never expire)."""
        req.done = True
        req.expired = True
        req.finish_t = now
        self.counters["deadline_expired"] += 1
        if req.deadline_s is not None:
            self._slo[0] += 1       # a deadline seen and MISSED
        if req.req_id in self.mgr.tables:     # defensive: resume state
            self.mgr.release(req.req_id)
        if self._obs is not None:
            self._obs.timeline.record(
                "expired", req.req_id, priority=req.priority,
                waited_ms=round((now - req.submit_t) * 1e3, 3))

    def _preempt_candidate(self, req: Request) -> Optional[int]:
        """The decode slot a waiting ``req`` may evict: the strictly
        lower-priority (HIGHER class) live decode slot, worst class
        first, latest-admitted within a class (least progress lost).
        Raw classes compare — aging promotes queue ORDER, not the right
        to evict running work. None when no slot is evictable (never,
        with recurrent layers: see :meth:`_preempt`)."""
        if self._pattern is not None and self._pattern.recurrent_layers:
            return None
        cand = [(s.req.priority, s.req.admit_t or 0.0, i)
                for i, s in enumerate(self._slots)
                if s.phase == "decode"]
        if not cand:
            return None
        cls, _, slot_id = max(cand)
        return slot_id if cls > req.priority else None

    def _preempt(self, slot_id: int) -> int:
        """Evict a decode slot: the victim's KV pages stay attached in
        the BlockManager and its decode carry (seq_len, last token) is
        saved on the request, so the requeued entry — re-inserted at
        its ORIGINAL line position within its class — resumes decode
        bit-identically to the un-preempted run."""
        if self._pattern is not None:
            self._pattern.refuse_preemption()
        slot = self._slots[slot_id]
        req = slot.req
        req.resume = (slot.seq_len, int(self._h_tok[slot_id]))
        req.preemptions += 1
        self.counters["preemptions"] += 1
        self.counters["requeues"] += 1
        # requeue the request's ORIGINAL entry: class, submit time and
        # line seq survive, the requeue count ticks, and started=True
        # exempts it from deadline expiry (its admission SLO was met)
        self._queue.requeue(req.qentry)
        if self._obs is not None:
            self._obs.timeline.record(
                "preempt", req.req_id, slot=slot_id,
                priority=req.priority,
                gen_tokens=len(req.tokens), seq_len=slot.seq_len)
        self._clear_slot(slot_id)
        return slot_id

    def _admit_resume(self, slot_id: int, req: Request, now: float):
        """Re-enter decode from saved carry: the slot gets exactly the
        values the vacated slot held (or, for a disaggregated handoff,
        the prefill group's first-token carry), so the decode stream
        continues bit-identically."""
        seq_len, tok = req.resume
        req.resume = None
        table = self.mgr.tables.get(req.req_id)
        if not table:
            raise RuntimeError(
                f"resume of request {req.req_id} without attached KV "
                "pages — preemption must retain the victim's pages")
        slot = self._slots[slot_id]
        slot.req = req
        slot.phase = "decode"
        slot.seq_len = seq_len
        slot.prefill_pos = int(req.prompt.size)
        self._slot_tables[slot_id] = 0
        self._slot_tables[slot_id, :len(table)] = table
        self._slot_wtables[slot_id] = self._slot_tables[slot_id]
        self._h_tok[slot_id] = tok
        self._h_seq[slot_id] = seq_len
        self._h_tables[slot_id] = self._slot_tables[slot_id]
        self._h_temps[slot_id] = self._temp_of(req.gen)
        self._dirty = True
        if self.mgr.window is not None:     # the pages it kept
            self._h_wtab[slot_id] = self.mgr.window.row(req.req_id)
            self._dirty_w = True
        self._record_admit(req, slot_id, now)

    def _run_prefill(self) -> bool:
        obs = self._obs
        for slot_id, slot in enumerate(self._slots):
            if slot.phase != "prefill":
                continue
            req = slot.req
            S = req.prompt.size
            pos0 = slot.prefill_pos
            with span("serve/prefill_stage", obs):
                n = min(S - pos0, self.buckets[-1])
                P = self._bucket_for(n)
                # the program cache keys the bucket AND the kernel
                # route (force pins / VMEM budget / interpret override)
                # exactly like generation.py's _PAGED_CACHE: a program
                # traced under a pin must not be replayed for unpinned
                # calls
                pk = (P,) + self._prefill_route_key()
                fn = self._prefill_fns.get(pk)
                if fn is None:
                    fn = self._prefill_fns[pk] = self._make_prefill_fn(P)
                    self._prefill_kind[pk] = (
                        "pallas" if self._prefill_fused_for(P) else "ref")
                toks = np.zeros((1, P), np.int32)
                toks[0, :n] = req.prompt[pos0:pos0 + n]
                # pos0/last_idx ride at the platform default int width
                # so the literal indices inside cached_forward's dynamic
                # slices promote consistently whether or not x64 is on
                if self.mgr.window is not None:
                    self._window_advance([(slot_id, pos0, pos0 + n)])
                args = (jnp.asarray(toks), jnp.asarray(pos0),
                        jnp.asarray(self._slot_tables[slot_id].copy()),
                        jnp.asarray(self._slot_wtables[slot_id].copy()),
                        jnp.asarray(n - 1),
                        jnp.asarray(self._temp_of(req.gen), jnp.float32))
            if self._flight is not None:
                inv = self._coll_prefill.get(P)
                if inv is None:
                    inv = self._coll_prefill[P] = tuple(
                        self._mesh.collective_inventory(self.cfg, B=1,
                                                        chunk=P))
                tasks = self._record_collectives(inv)
            else:
                tasks = None
            # host dispatch time only (the chunk completes async on
            # device; forcing it here would ADD a sync to the loop)
            with span("serve/prefill_dispatch", obs,
                      hist="prefill_chunk_ms", ring=False,
                      req_id=req.req_id, pos0=pos0, n=n,
                      bucket=P) as disp:
                args = (self.params, *args, self._d_key, self._k_pools,
                        self._v_pools)
                if self._state is not None:
                    args += (jnp.asarray(slot_id, jnp.int32), self._state)
                if pk not in self._prefill_noted and tracing():
                    self._prefill_noted.add(pk)
                    self._program_keys.append(_programs.note(fn, args))
                out = fn(*args)
                tok, self._d_key, self._k_pools, self._v_pools = out[:4]
                if self._state is not None:
                    self._state = out[4]
            self._end_collectives(tasks)
            self.counters["prefill_chunks"] += 1
            self.counters["prefill_tokens"] += n
            self.counters["prefill_pad_tokens"] += P - n
            if obs is not None:
                obs.timeline.record(
                    "prefill_chunk", req.req_id, dur_ms=disp.dur_ms,
                    pos0=pos0, n=n, bucket=P,
                    variant=self._prefill_kind.get(pk, "ref"))
            slot.prefill_pos += n
            if slot.prefill_pos < S:
                # mid-prompt chunk done: the chunked-prefill handoff
                # hook (disagg.py streams completed pages to the decode
                # group while later chunks still run). No-op here.
                self._on_prefill_chunk(slot_id)
            if slot.prefill_pos == S:
                with span("serve/first_token_sync", obs,
                          req_id=req.req_id):
                    first = int(np.asarray(tok))
                req.first_token_t = self._clock()
                req.ttft = req.first_token_t - req.submit_t
                req.tokens.append(first)
                if obs is not None:
                    obs.timeline.record(
                        "first_token", req.req_id,
                        ttft_ms=round(req.ttft * 1e3, 3))
                self.counters["tokens_generated"] += 1
                slot.seq_len = S
                if self._pcache is not None:
                    # the prompt's KV is fully valid NOW — index it so
                    # concurrent requests sharing the prefix hit while
                    # this one is still decoding. Decode appends at
                    # positions >= S, beyond every position the tree
                    # claims of these pages, so sharing them live is
                    # safe; _finish later extends the index with the
                    # generated tokens.
                    self._pcache.insert(
                        req.prompt,
                        list(self.mgr.tables.get(req.req_id, ())))
                self._on_prefill_complete(slot_id, first)
            return True
        return False

    def _on_prefill_chunk(self, slot_id: int):
        """Hook: one mid-prompt prefill chunk completed (the slot's
        ``prefill_pos`` already advanced, more prompt remains). The
        disaggregated prefill worker overrides this to stream the
        chunk's completed KV pages to the decode group."""

    def offload_metrics(self) -> Dict:
        """The host-tier report the fleet aggregates across replicas:
        page counts from the radix tree + bytes from the engine
        counters. All zeros without ``kv_offload``."""
        pc = self._pcache.stats if self._pcache is not None else {}
        return {
            "spilled_pages": pc.get("spilled_pages", 0),
            "restored_pages": pc.get("restored_pages", 0),
            "readopted_pages": pc.get("readopted_pages", 0),
            "host_evicted_pages": pc.get("host_evicted_pages", 0),
            "host_pages": (self._pcache.host_pages
                           if self._pcache is not None else 0),
            "spill_bytes": self.counters["kv_spill_bytes"],
            "restore_bytes": self.counters["kv_restore_bytes"],
        }

    def _on_prefill_complete(self, slot_id: int, first: int):
        """Prompt fully prefilled and first token sampled: transition
        the slot to decode (or finish on EOS / single-token budget).
        The disaggregated prefill worker overrides this to hand the
        request's KV pages to the decode group instead."""
        slot = self._slots[slot_id]
        req = slot.req
        if (first == req.gen.eos_token_id
                or req.gen.max_new_tokens <= 1):
            self._finish(slot_id)
        else:
            slot.phase = "decode"
            self._h_tok[slot_id] = first
            self._h_seq[slot_id] = slot.seq_len
            self._h_tables[slot_id] = self._slot_tables[slot_id]
            self._h_temps[slot_id] = self._temp_of(req.gen)
            self._dirty = True

    def _run_decode(self) -> bool:
        live = [i for i, s in enumerate(self._slots)
                if s.phase == "decode"]
        if not live:
            return False
        obs = self._obs
        route = kernel_route()
        if self._decode_route != route:
            # a registry pin, the VMEM budget or the interpret override
            # changed what dispatch would pick: trace again, never
            # replay the program the old route compiled
            self._decode_fn = self._make_decode_fn()
            self._decode_route = route
            self._decode_noted = False
        if self.mgr.window is not None:
            self._window_advance(
                [(i, self._slots[i].seq_len, self._slots[i].seq_len + 1)
                 for i in live], decode=True)
        if self._dirty:
            with span("serve/table_upload", obs):
                self._d_tok = self._upload(self._h_tok.copy())
                self._d_seq = self._upload(self._h_seq.copy())
                self._d_tables = self._upload(self._h_tables.copy())
                self._d_temps = self._upload(self._h_temps.copy())
            self._dirty = False
        tasks = self._record_collectives(self._coll_decode)
        with span("serve/decode_dispatch", obs, ring=False) as disp:
            args = (self.params, self._d_tok, self._d_seq, self._d_tables,
                    self._d_temps, self._d_key, self._k_pools,
                    self._v_pools,
                    *(() if self._state is None else (self._state,)))
            if not self._decode_noted and tracing():
                self._decode_noted = True
                self._program_keys.append(
                    _programs.note(self._decode_fn, args))
            out = self._decode_fn(*args)
            (self._d_tok, self._d_seq, self._d_key, self._k_pools,
             self._v_pools) = out[:5]
            if self._state is not None:
                self._state = out[5]
        with span("serve/token_sync", obs, ring=False) as sync:
            nxt = np.asarray(self._d_tok)       # the per-step host sync
        self._end_collectives(tasks)
        self.counters["decode_steps"] += 1
        self.counters["live_slot_steps"] += len(live)
        if obs is not None:
            # dispatch-to-sync wall time: the d2h read above already
            # synchronizes every step, so this measures real step
            # latency without adding any device round-trip
            dur_ms = disp.dur_ms + sync.dur_ms
            obs.hist("decode_step_ms").observe(dur_ms)
            # per-variant attribution, mirroring the prefill chunk's
            # ``variant`` stamp: which arm served this step
            # (tools/trace_summary.py --mode serving)
            obs.timeline.record("decode_step", dur_ms=dur_ms,
                                live_slots=len(live),
                                decode_variant=self._active_arm())
        with span("serve/emit", obs):
            for i in live:
                slot = self._slots[i]
                req = slot.req
                t = int(nxt[i])
                req.tokens.append(t)
                self.counters["tokens_generated"] += 1
                slot.seq_len += 1
                self._h_seq[i] = slot.seq_len
                self._h_tok[i] = t
                if (t == req.gen.eos_token_id
                        or len(req.tokens) >= req.gen.max_new_tokens):
                    self._finish(i)
        return True

    def _window_advance(self, spans, decode=False):
        """Before a program writes positions ``[start, stop)`` of each
        slot in ``spans``: give back the window pages that lie wholly
        behind what ``start``'s query still sees, take those up to
        ``stop``, and send the rows that changed. On a decode step also
        count what the window layers hold against what they would hold
        with nothing given back, and the global layers' live pages."""
        win, BS, c = self.mgr.window, self.block_size, self.counters
        with span("serve/window_release", self._obs):
            for slot_id, start, stop in spans:
                rid = self._slots[slot_id].req.req_id
                gone, changed = win.advance(
                    rid, start - (win.window - 1), stop)
                c["window_pages_released"] += gone
                if changed:
                    self._h_wtab[slot_id] = win.row(rid)
                    self._dirty_w = True
                if decode:
                    c["kv_tokens_seen_window"] += stop
                    c["kv_tokens_held_window"] += (
                        stop - win.first_block(rid) * BS)
                    c["kv_pages_live_global"] += -(-stop // BS)
            if self._dirty_w:
                self._state = {**self._state,
                               "win_tables": self._upload(
                                   self._h_wtab.copy())}
                self._dirty_w = False

    def _finish(self, slot_id: int):
        slot = self._slots[slot_id]
        req = slot.req
        req.done = True
        req.finish_t = self._clock()
        if self._obs is not None:
            n_gen = len(req.tokens)
            tpot_ms = (((req.finish_t - req.first_token_t)
                        / (n_gen - 1)) * 1e3
                       if n_gen > 1 and req.first_token_t is not None
                       else None)
            rec = {
                "req_id": req.req_id,
                "prompt_tokens": int(req.prompt.size),
                "gen_tokens": n_gen,
                "queue_wait_ms": (round((req.admit_t - req.submit_t)
                                        * 1e3, 3)
                                  if req.admit_t is not None else None),
                "ttft_ms": (round(req.ttft * 1e3, 3)
                            if req.ttft is not None else None),
                "tpot_ms": (round(tpot_ms, 3)
                            if tpot_ms is not None else None),
                "e2e_ms": round((req.finish_t - req.submit_t) * 1e3, 3),
                "priority": req.priority,
                **({"preemptions": req.preemptions}
                   if req.preemptions else {}),
            }
            # a request whose first token predates the last reset
            # carries a warmup-measured TTFT: keep its record but
            # exclude it from the histograms — the SAME predicate
            # metrics() uses for ttft_ms_mean/max, so the two never
            # disagree within one snapshot
            cut = self._metrics_reset_t
            self._obs.observe_request(
                rec, stale=(cut is not None
                            and req.first_token_t is not None
                            and req.first_token_t < cut))
            self._obs.timeline.record("finish", req.req_id,
                                      gen_tokens=n_gen)
        if self._pcache is not None and slot.seq_len > 0:
            # hand the pages to the radix tree instead of freeing them.
            # Valid KV covers prompt + all generated tokens except the
            # last sampled one (its KV was never written): that is
            # exactly slot.seq_len positions.
            gen_n = slot.seq_len - req.prompt.size
            seq = np.concatenate(
                [req.prompt, np.asarray(req.tokens[:gen_n], np.int32)])
            self._pcache.insert(
                seq, list(self.mgr.tables.get(req.req_id, ())))
        self.mgr.release(req.req_id)
        self._clear_slot(slot_id)
        self.counters["requests_completed"] += 1

    def _clear_slot(self, slot_id: int):
        """Vacate a slot WITHOUT touching the request's KV pages: the
        finish path releases them first; preemption and the
        disaggregated handoff deliberately keep them attached."""
        slot = self._slots[slot_id]
        slot.req = None
        slot.phase = "idle"
        slot.seq_len = 0
        slot.prefill_pos = 0
        self._slot_tables[slot_id] = 0
        self._slot_wtables[slot_id] = 0
        self._h_tok[slot_id] = 0
        self._h_seq[slot_id] = 0
        self._h_tables[slot_id] = 0
        self._h_temps[slot_id] = 0.0
        self._dirty = True          # vacated slot must not be written
        if self.mgr.window is not None:
            self._h_wtab[slot_id] = 0
            self._dirty_w = True

    # -- jitted programs ----------------------------------------------
    # decode step args: (params, tok, seq_lens, tables, temps, key,
    # k_pools, v_pools) -> (tok, seq_lens, key, k_pools, v_pools).
    # ONE declaration of which args are donated and which outputs feed
    # which args next call — _make_decode_fn and program_specs both
    # read these, so the audit spec cannot drift from the program
    _DECODE_DONATE = (1, 2, 5, 6, 7)
    _DECODE_CARRY = {0: 1, 1: 2, 2: 5, 3: 6, 4: 7}   # out idx -> argnum
    # prefill chunk args: (params, toks, pos0, table, wtable, last_idx,
    # temp, key, k_pools, v_pools) -> (tok, key, k_pools, v_pools)
    _PREFILL_DONATE = (7, 8, 9)
    _PREFILL_CARRY = {1: 7, 2: 8, 3: 9}

    def _dense_forward(self, params, tok, seq_lens, tables, k_pools,
                       v_pools):
        return _decode_step(params, tok, self.cfg, k_pools, v_pools,
                            tables, seq_lens, kv_scales=self._kv_scales)

    def _tp_forward(self, params, tok, seq_lens, tables, k_pools,
                    v_pools):
        """The same step per shard (inference/tp.py), under shard_map
        over the ServingMesh; sampling runs on the replicated logits
        outside it."""
        scales = self._kv_scales
        sharded = self._mesh.sharded_decode_fn(
            self.cfg, quant=scales is not None, params=self.params)
        return sharded(params, tok, seq_lens, tables, k_pools, v_pools,
                       *(scales or ()))

    def _hybrid_forward(self, params, tok, seq_lens, tables, k_pools,
                        v_pools, state):
        from .hybrid import decode_step
        return decode_step(params, tok, self.cfg, k_pools, v_pools,
                           tables, seq_lens, state)

    def program_scopes(self):
        """This engine's compiled programs as a reader of a device
        trace needs them (``observability.programs.Program``: the
        module's name as the trace prints it, ``{instruction name:
        scope}``): ``prog.scope("%fusion.121 = ...")`` beside an open
        profile says which ``PROGRAM_SCOPES`` name issued an operation.
        Parsed on the first call, never on a step."""
        return _programs.scopes(self._program_keys)

    def _make_decode_fn(self, record_variant=True):
        """THE decode program: one jitted ``step`` around the forward
        picked in ``__init__``. A model with recurrent layers carries
        the slots' state (inference/hybrid.py) as one more donated
        argument and output. Admission/completion never change shapes,
        so steady state stays zero retraces."""
        from ..ops.pallas.fused_decode_block import launch_operands
        from ..ops.pallas.registry import KERNELS
        counters, forward = self.counters, self._decode_forward

        def step(params, tok, seq_lens, tables, temps, key,
                 k_pools, v_pools, *state):
            counters["decode_traces"] += 1
            with KERNELS.record() as picked:
                logits, k_pools, v_pools, *state = forward(
                    params, tok, seq_lens, tables, k_pools, v_pools,
                    *state)
            if record_variant:
                # what dispatch picked for THIS trace. Audit clones
                # (program_specs) trace under their own pins/env and
                # must not clobber the live report
                self._decode_variant = {
                    "attn": picked.get("paged_attention_decode"),
                    "mlp": picked.get("decode_mlp_block", "unfused"),
                    "operands": launch_operands(picked),
                    "qkv": ("fused_stack"
                            if "qkv_proj" in params.get("layers", {})
                            else "per_leaf"),
                    # a model with an expert half: its grouped product
                    **({"experts": picked["moe_experts"]}
                       if "moe_experts" in picked else {})}
            with jax.named_scope("sample"):
                key, sub = jax.random.split(key)
                nxt = _sample_slots(logits, sub, temps)
                # inactive (padded) slots hold seq 0 and stay there;
                # their write above landed in scratch page 0, never read
                seq_lens = jnp.where(seq_lens > 0, seq_lens + 1, 0)
            return (nxt, seq_lens, key, k_pools, v_pools, *state)

        # donate the whole carried state, not just the pools: tok/seq/
        # key are replaced by this call's outputs every step (on host
        # mutation the mirrors re-upload fresh arrays), so the old
        # buffers update in place — the donation audit's own finding
        donate = self._DECODE_DONATE + (
            (8,) if self._pattern is not None else ())
        return jax.jit(step, donate_argnums=donate)

    def _make_prefill_fn_hybrid(self, P: int):
        """The chunk program of a model with recurrent layers: the
        chunk reads and writes its slot's recurrent state, so a prompt
        longer than a bucket continues its recurrence across chunks,
        and bucket padding advances nothing."""
        from .hybrid import prefill_chunk
        cfg, counters = self.cfg, self.counters
        counters["prefill_traces"].setdefault(P, 0)

        def chunk(params, toks, pos0, table, wtable, last_idx, temp,
                  key, k_pools, v_pools, slot, state):
            counters["prefill_traces"][P] += 1
            n_valid = jnp.asarray(last_idx, jnp.int32) + jnp.int32(1)
            lg, k_pools, v_pools, state = prefill_chunk(
                params, toks[0], cfg, k_pools, v_pools, table, wtable,
                pos0, n_valid, slot, state)
            with jax.named_scope("sample"):
                key, sub = jax.random.split(key)
                tok = _sample_slots(lg, sub, temp[None])[0]
            return tok, key, k_pools, v_pools, state

        return jax.jit(chunk, donate_argnums=self._PREFILL_DONATE + (11,))

    def _prefill_route_key(self):
        """The fused-prefill route's contribution to the per-bucket
        program cache key (empty when the knob is off)."""
        return _prefill_route(self._fused_prefill) \
            if (self._fused_prefill and self._prefill_mesh_ok) else ()

    def _prefill_meta(self, P: int):
        from ..ops.pallas.fused_prefill_block import prefill_meta
        return prefill_meta(self.cfg, P, self.block_size,
                            self.max_blocks, self._k_pools.dtype,
                            self._quant, weight_dtype=self._wq)

    def _prefill_fused_for(self, P: int) -> bool:
        """Whether bucket ``P``'s chunk program should be the
        pool-direct fused one: ALL-OR-NOTHING — both prefill-block ops
        must resolve to the Pallas megakernels, otherwise the verbatim
        pre-fusion chunk runs (bit-identical by construction)."""
        if not self._fused_prefill or not self._prefill_mesh_ok:
            return False
        from ..ops.pallas.fused_prefill_block import (
            prefill_fused_selected)
        return prefill_fused_selected(self._prefill_meta(P),
                                      self._fused_prefill)

    @property
    def prefill_variant(self) -> Dict:
        """Which prefill-chunk implementation this engine's bucket
        programs run: ``{"mode": ..., "attn": ..., "mlp": ...}`` —
        captured when a fused chunk TRACES (the decode_variant
        contract); before that, what dispatch would pick now for the
        largest bucket."""
        if not self._fused_prefill or not self._prefill_mesh_ok:
            return {"mode": "unfused", "attn": "unfused",
                    "mlp": "unfused"}
        if self._prefill_variant is not None:
            return dict(self._prefill_variant)
        from ..ops.pallas.fused_prefill_block import (
            resolve_prefill_blocks)
        _, _, names = resolve_prefill_blocks(
            self._prefill_meta(self.buckets[-1]), self._fused_prefill)
        return {"mode": str(self._fused_prefill), **names}

    def _make_prefill_fn_fused(self, P: int, record_variant=True):
        """The pool-direct fused chunk program for bucket ``P``: same
        signature, donation and <=1-trace-per-bucket contract as the
        unfused chunk, but per layer ONE fused attention kernel over
        the paged history + ONE fused MLP kernel, with the chunk's K/V
        scattered through the WRITE table (only the chunk's own
        positions move — not the whole dense view) and ragged
        valid-length bounds skipping pad compute."""
        cfg, counters = self.cfg, self.counters
        MB, BS = self.max_blocks, self.block_size
        scales = self._kv_scales
        mode = self._fused_prefill
        counters["prefill_traces"].setdefault(P, 0)

        def chunk(params, toks, pos0, table, wtable, last_idx, temp,
                  key, k_pools, v_pools):
            counters["prefill_traces"][P] += 1
            if record_variant:
                # trace-time snapshot: the same dispatch the forward
                # below consults, captured in the same context (the
                # decode_variant idiom; audit clones must not clobber)
                from ..ops.pallas.fused_prefill_block import (
                    resolve_prefill_blocks)
                _, _, names = resolve_prefill_blocks(
                    self._prefill_meta(P), mode)
                self._prefill_variant = {"mode": str(mode), **names}
            n_valid = (jnp.asarray(last_idx, jnp.int32)
                       + jnp.int32(1))
            logits, k_pools, v_pools = _fused_prefill_forward(
                params, toks[0], cfg, k_pools, v_pools, table, wtable,
                pos0, n_valid, kv_scales=scales, mode=mode)
            with jax.named_scope("sample"):
                lg = jax.lax.dynamic_slice_in_dim(logits, last_idx, 1,
                                                  axis=0)
                key, sub = jax.random.split(key)
                tok = _sample_slots(lg, sub, temp[None])[0]
            return tok, key, k_pools, v_pools

        return jax.jit(chunk, donate_argnums=self._PREFILL_DONATE)

    def _make_prefill_fn(self, P: int, record_variant=True):
        if self._pattern is not None:
            return self._make_prefill_fn_hybrid(P)
        if self._prefill_fused_for(P):
            return self._make_prefill_fn_fused(
                P, record_variant=record_variant)
        if self._mesh is not None:
            return self._make_prefill_fn_tp(P)
        return self._make_prefill_fn_ref(P)

    def _make_prefill_fn_ref(self, P: int):
        """The verbatim pre-fusion chunk: gather the request's pages
        into a dense view, run ``cached_forward``, scatter the whole
        view back through the WRITE table — the fused path's
        bit-identical fallback."""
        cfg, counters = self.cfg, self.counters
        MB, BS = self.max_blocks, self.block_size
        L, KV, hd = (cfg.num_hidden_layers, cfg.num_key_value_heads,
                     cfg.head_dim)
        scales = self._kv_scales
        counters["prefill_traces"].setdefault(P, 0)

        def chunk(params, toks, pos0, table, wtable, last_idx, temp, key,
                  k_pools, v_pools):
            counters["prefill_traces"][P] += 1
            # this request's pages as a dense [L, 1, T, KV, hd] cache:
            # the chunk runs the SAME cached_forward math as generate()'s
            # prefill, so single-request outputs match token-for-token
            with jax.named_scope("kv_gather"):
                kc = jnp.take(k_pools, table, axis=1) \
                    .reshape(L, 1, MB * BS, KV, hd)
                vc = jnp.take(v_pools, table, axis=1) \
                    .reshape(L, 1, MB * BS, KV, hd)
                if scales is not None:
                    kc = dequant_cache(kc, scales[0]).astype(cfg.dtype)
                    vc = dequant_cache(vc, scales[1]).astype(cfg.dtype)
            logits, kc, vc = cached_forward(params, toks, cfg, kc, vc,
                                            pos0)
            # the scatter goes through the WRITE table: entries backed
            # by shared prefix-cache pages are redirected to scratch
            # page 0 there, so the chunk cannot corrupt a shared page
            # (without a prefix cache wtable == table)
            with jax.named_scope("kv_scatter"):
                if scales is not None:
                    kc = quant_cache(kc, scales[0])
                    vc = quant_cache(vc, scales[1])
                k_pools = k_pools.at[:, wtable].set(
                    kc.reshape(L, MB, BS, KV, hd).astype(k_pools.dtype))
                v_pools = v_pools.at[:, wtable].set(
                    vc.reshape(L, MB, BS, KV, hd).astype(v_pools.dtype))
            # sample the request's FIRST token from the last valid
            # position (only meaningful on the final chunk)
            with jax.named_scope("sample"):
                lg = jax.lax.dynamic_slice_in_dim(logits, last_idx, 1,
                                                  axis=1)[:, 0]
                key, sub = jax.random.split(key)
                tok = _sample_slots(lg, sub, temp[None])[0]
            return tok, key, k_pools, v_pools

        # key is carried state exactly like the pools: the caller
        # rebinds self._d_key to the returned key, so donate it too
        return jax.jit(chunk, donate_argnums=self._PREFILL_DONATE)

    def _make_prefill_fn_tp(self, P: int):
        """Tensor-parallel bucketed prefill chunk: the per-shard body
        gathers the request's pages into a LOCAL dense view (the page
        indices are host-global; each shard holds its slice of the
        head axis), runs the tensor-parallel ``cached_forward`` mirror
        and scatters back through the WRITE table — same signature,
        donation and <=1-trace-per-bucket contract as the single-device
        chunk."""
        from .tp import _tp_cached_forward
        cfg, counters = self.cfg, self.counters
        MB, BS = self.max_blocks, self.block_size
        L, hd = cfg.num_hidden_layers, cfg.head_dim
        scales = self._kv_scales
        sm = self._mesh
        counters["prefill_traces"].setdefault(P, 0)
        rep = sm.replicated
        in_specs = (sm.param_specs(cfg, self.params), rep, rep, rep,
                    rep, sm.pool_spec, sm.pool_spec)
        if scales is not None:
            in_specs += (sm.scale_spec, sm.scale_spec)

        def fwd(params, toks, pos0, table, wtable, k_pools, v_pools,
                *sc):
            KV_l = k_pools.shape[3]       # local KV heads of this shard
            with jax.named_scope("kv_gather"):
                kc = jnp.take(k_pools, table, axis=1) \
                    .reshape(L, 1, MB * BS, KV_l, hd)
                vc = jnp.take(v_pools, table, axis=1) \
                    .reshape(L, 1, MB * BS, KV_l, hd)
                if sc:
                    kc = dequant_cache(kc, sc[0]).astype(cfg.dtype)
                    vc = dequant_cache(vc, sc[1]).astype(cfg.dtype)
            logits, kc, vc = _tp_cached_forward(
                params, toks, cfg, kc, vc, pos0, axis=sm.axis,
                collective=sm.collective)
            with jax.named_scope("kv_scatter"):
                if sc:
                    kc = quant_cache(kc, sc[0])
                    vc = quant_cache(vc, sc[1])
                k_pools = k_pools.at[:, wtable].set(
                    kc.reshape(L, MB, BS, KV_l, hd).astype(k_pools.dtype))
                v_pools = v_pools.at[:, wtable].set(
                    vc.reshape(L, MB, BS, KV_l, hd).astype(v_pools.dtype))
            return logits, k_pools, v_pools

        sharded = shard_map_norep(fwd, sm.mesh, in_specs,
                                  (rep, sm.pool_spec, sm.pool_spec))

        def chunk(params, toks, pos0, table, wtable, last_idx, temp,
                  key, k_pools, v_pools):
            counters["prefill_traces"][P] += 1
            extra = tuple(scales) if scales is not None else ()
            logits, k_pools, v_pools = sharded(
                params, toks, pos0, table, wtable, k_pools, v_pools,
                *extra)
            with jax.named_scope("sample"):
                lg = jax.lax.dynamic_slice_in_dim(logits, last_idx, 1,
                                                  axis=1)[:, 0]
                key, sub = jax.random.split(key)
                tok = _sample_slots(lg, sub, temp[None])[0]
            return tok, key, k_pools, v_pools

        return jax.jit(chunk, donate_argnums=self._PREFILL_DONATE)

    def _calibrate(self, prompt: np.ndarray):
        cfg, counters = self.cfg, self.counters
        P = self._bucket_for(min(int(prompt.size), self.buckets[-1]))
        if self._calib_fn is None or self._calib_bucket != P:
            def calib(params, toks):
                counters["calibration_traces"] += 1
                kc, vc = init_cache(cfg, 1, toks.shape[1],
                                    dtype=cfg.dtype)
                _, kc, vc = cached_forward(params, toks, cfg, kc, vc, 0)
                amax = lambda x: jnp.max(                  # noqa: E731
                    jnp.abs(x.astype(jnp.float32)), axis=(1, 2, 4))
                return amax(kc), amax(vc)
            self._calib_fn = jax.jit(calib)
            self._calib_bucket = P
        toks = np.zeros((1, P), np.int32)
        n = min(int(prompt.size), P)
        toks[0, :n] = prompt[:n]
        k_amax, v_amax = self._calib_fn(self.params, jnp.asarray(toks))
        self._kv_scales = (jnp.maximum(k_amax / 127.0, 1e-8),
                           jnp.maximum(v_amax / 127.0, 1e-8))

    # -- static program audit -----------------------------------------
    def program_specs(self, register: bool = True):
        """:class:`paddle_tpu.analysis.ProgramSpec` entries for the
        engine's jitted programs — the decode step, one prefill per
        bucket, and (with a prefix cache) the COW page copier — with
        abstract signatures derived from the engine's own shapes. The
        fns are FRESH jit instances, so auditing them can never disturb
        the live programs' compilation caches; their traced python
        bodies do tick the trace counters, which :meth:`audit`
        snapshots and restores."""
        from ..analysis import ProgramSpec, REGISTRY, abstract_signature
        sds = jax.ShapeDtypeStruct
        C, MB = self.capacity, self.max_blocks
        params_sd = abstract_signature(self.params)
        pools_sd = abstract_signature(self._k_pools)
        key_sd = abstract_signature(self._d_key)
        n_p = len(jax.tree_util.tree_leaves(params_sd))
        # arg 0 is the params pytree (n_p flat leaves); every later
        # arg is a single leaf, so argnum k>0 sits at flat index
        # n_p + (k - 1) — the class-level carry maps (argnum-keyed, the
        # same declarations the jit donate_argnums read) convert here
        flat = lambda argnum: n_p + argnum - 1          # noqa: E731
        # a mesh'd engine suffixes its programs _tp (the
        # collective-consistency rule gates the sharded programs
        # against the DECLARED axes)
        sm = self._mesh
        tp_sfx = "_tp" if sm is not None else ""
        axes = (sm.axis,) if sm is not None else ()
        tags = ("serving",) + (("tp",) if sm is not None else ())
        # a forced-pallas-PREFILL engine registers its bucket programs
        # under their own name the same way (the audit gate covers the
        # fused chunk next to, not instead of, the default program)
        prefill_base = ("serving_prefill_fused"
                        if self._fused_prefill in ("pallas",)
                        else "serving_prefill")
        # a pattern-run model: its state (a recurrent state a slot, the
        # window layers' pools and rings) is one more
        # donated argument (a pytree) behind each program's own, and
        # its leaves come back as the outputs after the program's own
        decode_extra = prefill_extra = ()
        decode_donate, prefill_donate = (self._DECODE_DONATE,
                                         self._PREFILL_DONATE)
        decode_carry = {o: flat(a) for o, a in self._DECODE_CARRY.items()}
        prefill_carry = {o: flat(a)
                         for o, a in self._PREFILL_CARRY.items()}
        if self._pattern is not None:
            state_sd = abstract_signature(self._state)
            n_s = len(jax.tree_util.tree_leaves(state_sd))
            decode_extra = (state_sd,)
            prefill_extra = (sds((), jnp.int32), state_sd)
            decode_donate += (8,)
            prefill_donate += (11,)
            decode_carry.update({5 + i: flat(8) + i for i in range(n_s)})
            prefill_carry.update({4 + i: flat(11) + i
                                  for i in range(n_s)})
        specs = [ProgramSpec(
            name="serving_decode" + tp_sfx, fn=self._make_decode_fn(
                record_variant=False),
            args=(params_sd, sds((C,), jnp.int32), sds((C,), jnp.int32),
                  sds((C, MB), jnp.int32), sds((C,), jnp.float32),
                  key_sd, pools_sd, pools_sd) + decode_extra,
            donate_argnums=decode_donate, carry=decode_carry,
            mesh_axes=axes, tags=tags)]
        # pos0/last_idx ride at the platform default int width
        # (serving._run_prefill stages them with a bare jnp.asarray)
        idx_dt = jnp.asarray(0).dtype
        for P in self.buckets:
            specs.append(ProgramSpec(
                name=f"{prefill_base}{tp_sfx}_{P}",
                fn=self._make_prefill_fn(P, record_variant=False),
                args=(params_sd, sds((1, P), jnp.int32), sds((), idx_dt),
                      sds((MB,), jnp.int32), sds((MB,), jnp.int32),
                      sds((), idx_dt), sds((), jnp.float32), key_sd,
                      pools_sd, pools_sd) + prefill_extra,
                donate_argnums=prefill_donate, carry=prefill_carry,
                mesh_axes=axes, tags=tags))
        if self._pcache is not None:
            specs.append(ProgramSpec(
                name="serving_page_copy" + tp_sfx, fn=self._copy_fn,
                args=(pools_sd, pools_sd, sds((), jnp.int32),
                      sds((), jnp.int32)),
                donate_argnums=(0, 1), carry={0: 0, 1: 1},
                mesh_axes=axes, tags=tags))
        if self._kv_offload:
            # the host-tier handoff pair (fresh jit instances — the
            # disagg_kv_extract/insert idiom): a single-page gather out
            # of the pools and the donated single-page scatter back
            ext, ins = self._make_offload_fns()
            ps = self._k_pools.shape
            W = self._offload_window
            page_sd = sds((ps[0], W) + ps[2:], self._k_pools.dtype)
            idx_sd = sds((W,), jnp.int32)
            specs.append(ProgramSpec(
                name="serving_kv_spill_extract" + tp_sfx, fn=ext,
                args=(pools_sd, pools_sd, idx_sd),
                mesh_axes=axes, tags=tags + ("offload",)))
            specs.append(ProgramSpec(
                name="serving_kv_restore_insert" + tp_sfx, fn=ins,
                args=(pools_sd, pools_sd, idx_sd, page_sd, page_sd),
                donate_argnums=(0, 1), carry={0: 0, 1: 1},
                mesh_axes=axes, tags=tags + ("offload",)))
        if register:
            for s in specs:
                REGISTRY.register(s)
        return specs

    def audit(self, register: bool = True):
        """Static audit of every engine program (trace-only — nothing
        executes, live compiled programs are untouched, and the trace
        counters the tier-1 suite pins are snapshotted/restored).
        Returns the list of :class:`AuditReport`; the finding count
        lands in the ``audit_findings`` counter."""
        from ..analysis import audit_spec as _audit, publish_findings
        import copy
        snap = {k: copy.deepcopy(self.counters[k])
                for k in ("decode_traces", "prefill_traces",
                          "calibration_traces", "offload_traces")}
        try:
            reports = [_audit(s)
                       for s in self.program_specs(register=register)]
        finally:
            self.counters.update(snap)
        publish_findings(reports, counters=self.counters, obs=self._obs)
        return reports
