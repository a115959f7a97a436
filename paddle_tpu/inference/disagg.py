"""Disaggregated prefill/decode serving: split chip groups with
KV-page handoff.

PR 9 sharded the serving programs over a mesh, but prefill chunks and
decode steps still interleave on the SAME chips: every ``step()`` runs
one prefill chunk ahead of the decode dispatch, so one long prompt
stalls every in-flight decode slot behind a multi-hundred-ms chunk —
the classic TPOT-spike failure mode (the per-step sync point makes the
contention visible as inflated ``decode_step_ms``). Disaggregated
serving removes it structurally, the way the paper's reference stack
separates scheduling from execution (fleet executor / predictor split)
and ClusterFusion++ (PAPERS.md) keeps the decode chips on their fused
hot loop uninterrupted:

- a **prefill group** and a **decode group** — disjoint device sets,
  each a :class:`~paddle_tpu.inference.tp.ServingMesh` (tp >= 1) —
  each run their OWN compiled programs over their OWN paged KV pools.
  The prefill group runs only bucketed chunked prefill (plus int8
  calibration and the radix prefix cache); the decode group runs only
  the single jitted decode-step program.
- a finished prefill hands its KV pages to the decode group through a
  jitted **page-handoff** pair: ``extract`` gathers the request's
  pages from the prefill pools into a fixed-width page block (padded
  page indices read the scratch page, so ONE trace covers every
  request size), ``jax.device_put`` moves the block onto the decode
  group's sharding (device-to-device copy over ICI/DCN on real
  multi-chip; the same code path runs on forced-host CPU devices in
  tier-1), and ``insert`` scatters it into the decode pools — donated,
  so the decode pools update in place. **Page-table translation is
  host-side**: each group's ``BlockManager`` owns its own physical
  page numbering, the handoff allocates decode-side pages and writes
  the translated table, and the prefill side releases its pages (the
  radix prefix cache keeps its refcounted copies, so warm admissions
  keep working on the prefill side).
- the handoff is **async and double-buffered** (r16): a transfer's
  extract + device_put are ISSUED in one orchestrator step and its
  donated insert lands at the top of the NEXT step, so the
  device-to-device copy overlaps the prefill chunk and decode step
  dispatched in between instead of serializing ahead of them (at most
  two transfers in flight). The request's resume entry is pushed only
  when its final insert lands, so the decode group never sees
  half-arrived pages and the bit-parity contract is untouched.
- **chunked-prefill handoff** (r16): a multi-chunk prompt streams each
  completed chunk's full pages to the decode group while later chunks
  still run (same extract/put/insert programs, offset page windows),
  so a long prompt's bulk transfer stops serializing behind its last
  chunk in the handoff queue. Chunk boundaries rewrite already-filled
  positions with identical bytes (the gather/forward/scatter round
  trip is idempotent for untouched positions), so partial pages are
  final the moment their chunk completes. Opportunistic: partials ship
  only when the decode pool can already admit the whole request; a
  request that finishes ON the prefill group (EOS at first token)
  after shipping partials queues an abort marker that releases its
  decode-side pages after any in-flight inserts land.
- **SLO-aware admission** (inference/admission.py) is shared with the
  colocated engine: priority classes + per-request deadlines on
  ``submit()``, a priority queue with aging replacing FIFO, and
  preemption/requeue of decode slots under pressure — a victim keeps
  its KV pages and its decode carry, so the resumed decode stream is
  bit-identical to the un-preempted run.

Greedy parity: the prefill group runs the exact prefill math of the
colocated engine and the decode group the exact decode math; the
handoff moves raw page bytes. With single-device groups (or the
``"gather"`` collective placement) greedy output is therefore
BIT-identical to the colocated ``ServingEngine`` — asserted in tier-1
over mixed-arrival streams including the prefix-cache warm path and
int8 pools. Steady state is zero retraces per group: 1 decode program,
<=1 prefill program per bucket, plus the two handoff programs traced
once each.

Observability: both workers share the DisaggregatedEngine's timeline
ring and request-record log, handoff latency/bytes feed a bound flight
recorder (``kv_handoff@xfer``) plus the ``handoff_ms`` histogram, and
``metrics()`` composes the scheduler report (per-class queue wait, SLO
attainment, preemptions) with both groups' full engine metrics.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..observability import (Observability, TelemetryConfig,
                             TelemetryPlane)
from .generation import GenerationConfig
from .serving import (Request, ServingEngine, _collectives_snapshot,
                      _drain_loop)
from .tp import ServingMesh, normalize_mesh

__all__ = ["DisaggregatedEngine"]

# the engine-level latency set: request-level distributions (fed by
# whichever worker finishes/admits the request — the histogram objects
# are SHARED with both workers' registries) plus what only the
# orchestrator can time (handoff, whole-engine step)
DISAGG_HISTOGRAMS = ("ttft_ms", "tpot_ms", "queue_wait_ms", "e2e_ms",
                     "handoff_ms", "step_ms")
# the sub-set shared by reference with the workers' registries
_SHARED_HISTOGRAMS = ("ttft_ms", "tpot_ms", "queue_wait_ms", "e2e_ms")


class _HandoffJob:
    """One queued transfer: a slice of a request's prefill-side pages
    (``src_pages[offset:]``) bound for the decode group. ``final``
    carries the resume entry; ``abort`` releases the decode-side
    allocation of a request that finished on the prefill group after
    shipping partials."""

    __slots__ = ("req", "src_pages", "offset", "final", "abort")

    def __init__(self, req: Request, src_pages: List[int], offset: int,
                 final: bool, abort: bool = False):
        self.req = req
        self.src_pages = src_pages
        self.offset = int(offset)
        self.final = final
        self.abort = abort


class _PrefillWorker(ServingEngine):
    """The prefill-group half: a ServingEngine that allocates KV pages
    for the PROMPT only and, instead of transitioning a completed
    prefill into a decode slot, vacates the slot (pages stay attached)
    and hands the request to the DisaggregatedEngine's handoff queue.
    Mid-prompt chunks report through ``on_chunk`` (the chunked-prefill
    handoff). Requests that finish during prefill (EOS first token,
    single-token budget) complete here and never touch the decode
    group."""

    def __init__(self, *args, on_complete=None, on_chunk=None, **kw):
        self._on_complete_cb = on_complete
        self._on_chunk_cb = on_chunk
        super().__init__(*args, **kw)

    def _alloc_tokens(self, req: Request) -> int:
        return int(req.prompt.size)     # generation lives elsewhere

    def _on_prefill_chunk(self, slot_id: int):
        if self._on_chunk_cb is not None:
            slot = self._slots[slot_id]
            self._on_chunk_cb(
                slot.req,
                list(self.mgr.tables.get(slot.req.req_id, ())),
                slot.prefill_pos)

    def _on_prefill_complete(self, slot_id: int, first: int):
        slot = self._slots[slot_id]
        req = slot.req
        if (first == req.gen.eos_token_id
                or req.gen.max_new_tokens <= 1):
            self._finish(slot_id)       # done entirely on this group
            self._on_complete_cb(req, None)
            return
        pages = list(self.mgr.tables.get(req.req_id, ()))
        # vacate the slot but KEEP the pages attached — the handoff
        # owns their transfer to the decode group and their release
        self._clear_slot(slot_id)
        self._on_complete_cb(req, pages)


class DisaggregatedEngine:
    """Prefill/decode-disaggregated serving over two chip groups.

    Construction (one of):

    - ``prefill_devices=[...], decode_devices=[...]`` — explicit
      device lists (each becomes a tp=len(list) ServingMesh);
    - ``mesh=ServingMesh(...)`` (or a 1-D jax Mesh, or an int device
      count) + ``prefill_tp=k`` — the mesh's devices split into the
      first ``k`` (prefill) and the rest (decode);
    - neither — all visible devices split at ``prefill_tp``. A
      single-device environment falls back to both groups sharing the
      one device (programs and handoff identical in structure; only
      the physical overlap differs), so audits and catalogs build the
      same program set everywhere.

    ``submit()/step()/drain()/metrics()`` mirror the colocated
    :class:`ServingEngine` contract; ``priority``/``deadline_s`` ride
    per request (inference/admission.py semantics).
    """

    def __init__(self, params, cfg, prefill_devices=None,
                 decode_devices=None, mesh=None, prefill_tp: int = 1,
                 collective: str = "psum",
                 capacity: int = 4, prefill_slots: int = 2,
                 block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 prefill_num_blocks: Optional[int] = None,
                 max_seq_len: Optional[int] = None, cache_dtype=None,
                 prefill_buckets=(32, 128), seed: int = 0,
                 prefix_cache: bool = False, kv_offload=False,
                 observability=False,
                 fused_prefill=None, weight_quant=None,
                 aging_s: Optional[float] = None, telemetry=False,
                 clock=None):
        # injectable scheduler clock, threaded through BOTH group
        # workers (serving.py's seam): one fake clock drives every
        # submit_t/deadline/aging decision deterministically for tests
        # and the lifecycle model checker. None = wall clock.
        self._clock = clock if clock is not None else time.perf_counter
        pre_mesh, dec_mesh = self._resolve_groups(
            prefill_devices, decode_devices, mesh, prefill_tp,
            collective)
        # weight quantization: quantize ONCE here so both group
        # workers share the same tree (byte-identical scales on both
        # sides — the handoff's bit-parity contract needs the decode
        # group to continue exactly the prefill group's math); the
        # workers then adopt the carried mode
        from ..quantization.ptq import ensure_quantized
        params, self._weight_quant = ensure_quantized(params,
                                                      weight_quant)
        self.cfg = cfg
        self.counters = {
            "handoffs": 0, "partial_handoffs": 0, "handoff_traces": 0,
            "kv_bytes_transferred": 0, "requests_submitted": 0,
            "drain_truncations": 0,
        }
        # telemetry implies observability (alerts land timeline events
        # and stall dumps, both owned by the harness)
        _tcfg = TelemetryConfig.coerce(telemetry)
        if observability or _tcfg is not None:
            self._obs = (observability
                         if isinstance(observability, Observability)
                         else Observability(histograms=DISAGG_HISTOGRAMS))
            self._obs.registry.adopt_counters(self.counters)
            pre_obs: object = Observability()
            dec_obs: object = Observability()
        else:
            self._obs = None
            pre_obs = dec_obs = False
        self._flight = None
        if self._obs is not None:
            from ..distributed.flight_recorder import FlightRecorder
            rec = FlightRecorder(capacity=4096)
            rec.enabled = True
            self._flight = self._obs.bind_flight_recorder(rec)

        BS = int(block_size)
        msl = int(max_seq_len or cfg.max_position_embeddings)
        if prefill_num_blocks is None:
            # prompt pages for every prefill slot PLUS slack for pages
            # parked in the handoff queue while the decode pool pushes
            # back (vacated prefill slots keep refilling)
            prefill_num_blocks = \
                (int(prefill_slots) + int(capacity)) * (-(-msl // BS)) + 1
        self.prefill = _PrefillWorker(
            params, cfg, capacity=prefill_slots, block_size=BS,
            num_blocks=prefill_num_blocks, max_seq_len=msl,
            cache_dtype=cache_dtype, prefill_buckets=prefill_buckets,
            seed=seed, prefix_cache=prefix_cache, kv_offload=kv_offload,
            observability=pre_obs,
            fused_prefill=fused_prefill,
            mesh=pre_mesh, aging_s=aging_s, clock=clock,
            on_complete=self._on_prefilled,
            on_chunk=self._on_prefill_chunk)
        self.decode = ServingEngine(
            params, cfg, capacity=capacity, block_size=BS,
            num_blocks=num_blocks, max_seq_len=msl,
            cache_dtype=cache_dtype, prefill_buckets=prefill_buckets,
            seed=seed + 1, prefix_cache=False, observability=dec_obs,
            fused_prefill=fused_prefill,
            mesh=dec_mesh, aging_s=aging_s, clock=clock)
        if self._obs is not None:
            # one timeline ring + one request-record log for the whole
            # engine: both workers' events (submit/admit/prefill_chunk/
            # first_token/decode_step/preempt/resume/finish) interleave
            # with the orchestrator's handoff events, so one JSONL
            # export describes the full request lifecycle
            self.prefill._obs.timeline = self._obs.timeline
            self.decode._obs.timeline = self._obs.timeline
            self.prefill._obs.request_records = self._obs.request_records
            self.decode._obs.request_records = self._obs.request_records
            self._share_histograms()
        # continuous telemetry plane (r22): the orchestrator rollup
        # plus each group's engine under a `group` label, so a decode-
        # side regression is attributable without un-merging the rollup
        self._telemetry = None
        if _tcfg is not None:
            self._telemetry = TelemetryPlane(
                _tcfg, on_alert=self._telemetry_alert)
            self._telemetry.register("disagg_engine", self.metrics,
                                     counters=self.counters,
                                     skip=("groups",))
            self._telemetry.register(
                "disagg_group", self.prefill.metrics,
                labels={"group": "prefill"},
                counters=self.prefill.counters, skip=("groups",))
            self._telemetry.register(
                "disagg_group", self.decode.metrics,
                labels={"group": "decode"},
                counters=self.decode.counters, skip=("groups",))

        self.block_size = BS
        self.max_seq_len = msl
        self.capacity = int(capacity)
        self.prefill_slots = int(prefill_slots)
        self._quant = self.decode._quant
        # fixed handoff width = the largest prompt's page count; padded
        # entries index scratch page 0 on both sides, so ONE trace of
        # each handoff program covers every request size
        self._xfer_w = -(-msl // BS)
        self._extract_fn = None
        self._insert_fn = None
        self._handoffs: Deque[_HandoffJob] = deque()
        # started transfers whose donated insert lands at the top of
        # the NEXT step (async double-buffering: <= 2 in flight)
        self._inflight: Deque[Dict] = deque()
        self._partial_sent: Dict[int, int] = {}   # req_id -> pages sent
        self._requests: List[Request] = []
        self._hand_stats = [0, 0.0, 0.0]    # count, sum_ms, max_ms
        self._t_first = self._t_last = None
        self._metrics_reset_t = None
        self.last_drain_truncated = False

    # -- group resolution ---------------------------------------------
    @staticmethod
    def _resolve_groups(prefill_devices, decode_devices, mesh,
                        prefill_tp, collective):
        if prefill_devices is not None or decode_devices is not None:
            if not prefill_devices or not decode_devices:
                raise ValueError(
                    "explicit groups need BOTH prefill_devices and "
                    "decode_devices non-empty")
            mk = lambda d: ServingMesh.make(          # noqa: E731
                tp=len(d), collective=collective, devices=list(d))
            return mk(prefill_devices), mk(decode_devices)
        if isinstance(mesh, int):
            mesh = ServingMesh.make(tp=mesh, collective=collective)
        sm = normalize_mesh(mesh)
        if sm is None:
            devs = jax.devices()
            if len(devs) < 2:
                # single-device fallback: both groups share the one
                # device — program structure and the handoff path are
                # identical, so audits/catalogs build everywhere
                one = ServingMesh.make(tp=1, collective=collective,
                                       devices=devs)
                return one, one
            sm = ServingMesh.make(tp=len(devs), collective=collective,
                                  devices=devs)
        return sm.split(prefill_tp)

    # -- public API ---------------------------------------------------
    def submit(self, prompt, gen: Optional[GenerationConfig] = None,
               priority: Optional[int] = None,
               deadline_s: Optional[float] = None) -> Request:
        """Enqueue one request on the prefill group (the decode group
        admits it via KV handoff once its prompt is prefilled)."""
        gen = gen or GenerationConfig()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size >= 1:
            total = int(prompt.size) + int(gen.max_new_tokens)
            need = -(-total // self.decode.block_size)
            if need > self.decode.num_blocks - 1:
                raise ValueError(
                    f"request needs {need} KV pages but the DECODE "
                    f"group's pool only has {self.decode.num_blocks - 1}"
                    "; raise num_blocks")
        req = self.prefill.submit(prompt, gen, priority=priority,
                                  deadline_s=deadline_s)
        self._requests.append(req)
        self.counters["requests_submitted"] += 1
        return req

    def step(self) -> bool:
        """One orchestrator iteration: drain ready handoffs into the
        decode group, then one prefill-group step (admission + one
        chunk) and one decode-group step (resume admission + one decode
        step over all live slots) — the two groups' device work streams
        run concurrently, which is the whole point."""
        obs = self._obs
        t0 = self._clock() if obs is not None else 0.0
        if self._t_first is None:
            self._t_first = self._clock()
        did = self._run_handoffs()
        did = self.prefill.step() or did
        did = self.decode.step() or did
        if did:
            self._t_last = self._clock()
            if obs is not None:
                obs.hist("step_ms").observe(
                    (self._clock() - t0) * 1e3)
        if self._telemetry is not None:
            self._telemetry.on_step()
        return did

    @property
    def idle(self) -> bool:
        return (not self._handoffs and not self._inflight
                and self.prefill.idle and self.decode.idle)

    # -- fleet-router surface (inference/fleet.py) --------------------
    @property
    def queue_depth(self) -> int:
        """Un-admitted work anywhere in the engine: both groups'
        admission queues plus handoffs queued or in flight."""
        return (len(self.prefill._queue) + len(self.decode._queue)
                + len(self._handoffs) + len(self._inflight))

    @property
    def live_slots(self) -> int:
        return self.prefill.live_slots + self.decode.live_slots

    @property
    def prefix_cache_version(self) -> int:
        return self.prefill.prefix_cache_version

    def prefix_summary(self):
        """The radix tree lives on the prefill group (where admission
        happens) — its summary IS this engine's warm-state summary."""
        return self.prefill.prefix_summary()

    def offload_metrics(self) -> Dict:
        return self.prefill.offload_metrics()

    def drain(self, max_steps: Optional[int] = None) -> int:
        """Step until both groups and the handoff queue are empty
        (the shared :func:`_drain_loop` semantics: capped drains record
        truncation; starvation raises after a stall dump)."""
        return _drain_loop(
            self, max_steps,
            starve_reason="disaggregated drain starved: pending work "
                          "cannot progress",
            starve_error="disaggregated engine starved: pending "
                         "requests cannot be admitted or handed off "
                         "(KV pools too small for the in-flight mix?)")

    def _drain_truncated_event(self, n: int):
        if self._obs is not None:
            self._obs.timeline.record(
                "drain_truncated", steps=n,
                handoff_queue_depth=(len(self._handoffs)
                                     + len(self._inflight)))

    # -- handoff ------------------------------------------------------
    def _need_pages(self, req: Request) -> int:
        return -(-(int(req.prompt.size) + int(req.gen.max_new_tokens))
                 // self.decode.block_size)

    def _on_prefill_chunk(self, req: Request, pages: List[int],
                          pos: int):
        """Chunked-prefill handoff: a mid-prompt chunk completed —
        queue the prompt pages it finished (every position < ``pos``
        is final; later chunks rewrite them with identical bytes) as a
        partial transfer. Opportunistic: skipped unless the decode
        pool can already admit the WHOLE request, so a partial can
        never strand a half-transferred prompt against backpressure."""
        dec = self.decode
        done = pos // self.block_size
        sent = self._partial_sent.get(req.req_id, 0)
        if done <= sent:
            return
        if req.req_id not in dec.mgr.tables:
            if len(dec.mgr.free) < self._need_pages(req):
                return
            dec.mgr.allocate(req.req_id, int(req.prompt.size)
                             + int(req.gen.max_new_tokens))
        self._partial_sent[req.req_id] = done
        self._handoffs.append(
            _HandoffJob(req, pages[:done], sent, final=False))

    def _on_prefilled(self, req: Request, pages: Optional[List[int]]):
        sent = self._partial_sent.pop(req.req_id, 0)
        if pages is None:
            # finished on the prefill group. If partials already went
            # across, an abort marker releases the decode-side pages —
            # queued BEHIND them so it lands after their inserts.
            if req.req_id in self.decode.mgr.tables:
                self._handoffs.append(
                    _HandoffJob(req, [], sent, final=False, abort=True))
            return
        self._handoffs.append(_HandoffJob(req, pages, sent, final=True))

    def _next_startable_job(self) -> Optional[int]:
        """Index of the next job the transfer engine may start, or
        None. FIFO, except that a job which allocates NOTHING (abort,
        partial, or a final whose decode table already exists from its
        partials) may overtake a page-blocked head: its pages are
        already held, and completing it is the only way those pages
        ever free — the _admit resume-overtake idiom, without which a
        page-blocked short final ahead of a partial-allocated long
        final deadlocks the engine. An allocating final never
        overtakes (page fairness)."""
        dec = self.decode
        for i, job in enumerate(self._handoffs):
            needs_alloc = (job.final and not job.abort
                           and job.req.req_id not in dec.mgr.tables)
            if not needs_alloc:
                return i
            if i == 0 and (len(dec.mgr.free)
                           >= self._need_pages(job.req)):
                return i
            # page-blocked (or non-head) allocating final: waits
        return None

    def _run_handoffs(self) -> bool:
        """Land the inserts of transfers issued LAST step, then issue
        new ones (double-buffered: at most two in flight). The gap
        between issue and landing is where the device-to-device copy
        overlaps this step's prefill chunk and decode dispatch."""
        did = False
        while self._inflight:
            self._complete_transfer(self._inflight.popleft())
            did = True
        while self._handoffs and len(self._inflight) < 2:
            idx = self._next_startable_job()
            if idx is None:
                break       # decode-pool backpressure: finish frees
            job = self._handoffs[idx]
            del self._handoffs[idx]
            if job.abort:
                self._inflight.append({"job": job})
            else:
                self._inflight.append(self._start_transfer(job))
            did = True
        return did

    def _build_handoff_fns(self):
        """The jitted page-handoff pair. ``extract`` gathers a fixed-
        width block of pages from the prefill pools; ``insert``
        scatters it into the decode pools (donated — the pools update
        in place). Padded index entries point at scratch page 0 on
        both sides: the extra reads copy scratch bytes, the extra
        writes land in a page no live sequence ever reads — so one
        trace each covers every request size (the slot-table padding
        idiom)."""
        counters = self.counters

        def extract(kp, vp, idx):
            counters["handoff_traces"] += 1
            return (jnp.take(kp, idx, axis=1),
                    jnp.take(vp, idx, axis=1))

        def insert(kp, vp, idx, kpag, vpag):
            counters["handoff_traces"] += 1
            return (kp.at[:, idx].set(kpag), vp.at[:, idx].set(vpag))

        return (jax.jit(extract),
                jax.jit(insert, donate_argnums=(0, 1)))

    def _sync_scales(self):
        """Copy the prefill group's one-shot int8 calibration onto the
        decode group (before its decode program first traces, so the
        program closes over the final scale arrays) — the engine-global
        static-scale contract, now spanning two pools."""
        dm = self.decode._mesh
        self.decode._kv_scales = tuple(
            dm.shard(jnp.asarray(np.asarray(s)), dm.scale_spec)
            for s in self.prefill._kv_scales)

    def _start_transfer(self, job: _HandoffJob) -> Dict:
        """Issue one transfer's extract -> device_put (the insert lands
        next step): host-side page-table translation first (decode-side
        allocation, reused across a request's partial windows), then
        the jitted gather off the prefill pools and the async
        device-to-device copy onto the decode group's sharding. A FINAL
        job releases the request's prefill-side pages here — the
        extract already captured their bytes (functional arrays), and
        the radix tree's refcounted shares survive (warm prefix matches
        keep hitting on this group)."""
        pre, dec = self.prefill, self.decode
        req = job.req
        if self._extract_fn is None:
            self._extract_fn, self._insert_fn = self._build_handoff_fns()
        if self._quant and dec._kv_scales is None:
            self._sync_scales()
        t0 = self._clock()
        total = int(req.prompt.size) + int(req.gen.max_new_tokens)
        # decode-side allocation IS the page-table translation: the
        # request's table on this group is a fresh set of physical
        # pages; the first len(src_pages) receive the prompt's KV, the
        # rest are decode headroom. Partial windows extend one table.
        dst_table = dec.mgr.allocate(req.req_id, total)
        src = job.src_pages[job.offset:]
        n = len(src)
        W = self._xfer_w
        src_idx = np.zeros((W,), np.int32)
        dst_idx = np.zeros((W,), np.int32)
        src_idx[:n] = src
        dst_idx[:n] = dst_table[job.offset:job.offset + n]
        cfgv = self.cfg
        L, KV, hd = (cfgv.num_hidden_layers,
                     cfgv.num_key_value_heads, cfgv.head_dim)
        BS = self.block_size
        itemsize = jnp.dtype(pre._k_pools.dtype).itemsize
        nbytes = 2 * L * n * BS * KV * hd * itemsize
        task = None
        if self._flight is not None:
            task = self._flight.begin(
                "kv_handoff", "xfer", (2 * L, n * BS, KV * hd),
                str(jnp.dtype(pre._k_pools.dtype)))
        kpag, vpag = self._extract_fn(pre._k_pools, pre._v_pools,
                                      pre._mesh.replicate(src_idx))
        t1 = self._clock()
        sh = dec._mesh.sharding(dec._mesh.pool_spec)
        kpag = jax.device_put(kpag, sh)
        vpag = jax.device_put(vpag, sh)
        t2 = self._clock()
        if job.final:
            pre.mgr.release(req.req_id)
        return {"job": job, "kpag": kpag, "vpag": vpag,
                "dst_idx": dst_idx, "pages": n, "nbytes": nbytes,
                "task": task, "t0": t0, "t1": t1, "t2": t2}

    def _complete_transfer(self, st: Dict):
        """Land one transfer: the donated insert into the decode pools,
        then (final jobs only) the resume entry into the decode group's
        admission queue — pushed strictly after the insert, so the
        decode group never admits onto half-arrived pages. Abort
        markers release the decode-side allocation instead (their
        request finished on the prefill group)."""
        job = st["job"]
        req = job.req
        dec = self.decode
        if job.abort:
            dec.mgr.release(req.req_id)
            if self._obs is not None:
                self._obs.timeline.record("handoff_abort", req.req_id)
            return
        dec._k_pools, dec._v_pools = self._insert_fn(
            dec._k_pools, dec._v_pools,
            dec._mesh.replicate(st["dst_idx"]), st["kpag"], st["vpag"])
        t3 = self._clock()
        if st["task"] is not None:
            self._flight.end(st["task"])
        self.counters["kv_bytes_transferred"] += st["nbytes"]
        dur_ms = (t3 - st["t0"]) * 1e3
        phase_ms = {
            "extract_ms": round((st["t1"] - st["t0"]) * 1e3, 3),
            "put_ms": round((st["t2"] - st["t1"]) * 1e3, 3),
            "insert_ms": round((t3 - st["t2"]) * 1e3, 3),
        }
        if not job.final:
            self.counters["partial_handoffs"] += 1
            if self._obs is not None:
                self._obs.timeline.record(
                    "handoff_partial", req.req_id, dur_ms=dur_ms,
                    pages=st["pages"], bytes=st["nbytes"], **phase_ms)
            return
        # resume entry for the decode group: carry = (prompt length,
        # first sampled token) — exactly the colocated engine's
        # decode-entry state, so generation continues bit-identically.
        # started=True: the admission SLO was met at prefill admission
        req.resume = (int(req.prompt.size), int(req.tokens[-1]))
        req.qentry = dec._queue.push(req, cls=req.priority,
                                     submit_t=req.submit_t,
                                     started=True)
        self.counters["handoffs"] += 1
        hs = self._hand_stats
        hs[0] += 1
        hs[1] += dur_ms
        hs[2] = max(hs[2], dur_ms)
        if self._obs is not None:
            self._obs.hist("handoff_ms").observe(dur_ms)
            self._obs.timeline.record(
                "handoff", req.req_id, dur_ms=dur_ms,
                pages=st["pages"], bytes=st["nbytes"], **phase_ms)

    # -- reporting ----------------------------------------------------
    def scheduler_snapshot(self) -> Dict:
        return {"handoff_queue_depth": (len(self._handoffs)
                                        + len(self._inflight)),
                "handoff_inflight": len(self._inflight),
                "handoffs_pending": [j.req.req_id
                                     for j in list(self._handoffs)[:16]],
                "prefill": self.prefill.scheduler_snapshot(),
                "decode": self.decode.scheduler_snapshot()}

    def metrics(self) -> Dict:
        c = {k: v for k, v in self.counters.items()
             if k not in ("collective_calls", "collective_bytes")}
        pre_c, dec_c = self.prefill.counters, self.decode.counters
        wall = ((self._t_last - self._t_first)
                if self._t_first is not None
                and self._t_last is not None else 0.0)
        c["wall_time_s"] = round(wall, 6)
        gen_tokens = (pre_c["tokens_generated"]
                      + dec_c["tokens_generated"])
        c["tokens_generated"] = gen_tokens
        c["tokens_per_sec"] = (round(gen_tokens / wall, 3)
                               if wall > 0 else 0.0)
        c["requests_completed"] = (pre_c["requests_completed"]
                                   + dec_c["requests_completed"])
        cut = self._metrics_reset_t
        ttfts = [r.ttft for r in self._requests
                 if r.ttft is not None
                 and (cut is None or (r.first_token_t or 0.0) >= cut)]
        c["ttft_ms_mean"] = (round(float(np.mean(ttfts)) * 1e3, 3)
                             if ttfts else None)
        c["ttft_ms_max"] = (round(float(np.max(ttfts)) * 1e3, 3)
                            if ttfts else None)
        n, s, mx = self._hand_stats
        c["handoff_ms_mean"] = round(s / n, 3) if n else None
        c["handoff_ms_max"] = round(mx, 3) if n else None
        sched = self.prefill._scheduler_metrics()
        sched["preemptions"] = dec_c["preemptions"]
        sched["requeues"] = dec_c["requeues"]
        sched["deadline_expired"] = pre_c["deadline_expired"]
        sched["handoff_queue_depth"] = (len(self._handoffs)
                                        + len(self._inflight))
        c["scheduler"] = sched
        c["groups"] = {"prefill": self.prefill.metrics(),
                       "decode": self.decode.metrics()}
        # decode-variant roofline attribution belongs to the group
        # that runs decode steps (both groups also carry their own
        # under c["groups"])
        c["roofline"] = self.decode._roofline_metrics()
        if self._obs is not None:
            obs = self._obs
            c["latency"] = obs.latency_snapshot()
            c["retrace_warnings"] = (
                len(self.prefill._obs.watchdog.events)
                + len(self.decode._obs.watchdog.events))
            c["stall_dumps"] = (len(obs.stall_dumps)
                                + obs.stall_dumps_suppressed)
            c["timeline_events"] = len(obs.timeline)
            c["timeline_dropped"] = obs.timeline.dropped
            if self._flight is not None:
                c["collectives"] = _collectives_snapshot(self.counters,
                                                         obs)
        if self._telemetry is not None:
            c["telemetry"] = self._telemetry.snapshot()
        return c

    @property
    def telemetry(self) -> Optional[TelemetryPlane]:
        """The continuous telemetry plane, or None when disabled."""
        return self._telemetry

    def _telemetry_alert(self, alert: Dict):
        """Stamp an ``alert`` timeline event; page-severity alerts also
        land a flight-recorder dump with the whole-engine scheduler
        snapshot (both groups + handoff queue)."""
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
                f"{alert.get('metric')}", self.scheduler_snapshot(),
                metrics={"alert": alert})

    def reset_metrics(self):
        """Restart the measurement window on the orchestrator AND both
        groups (each group's retrace watchdog arms; the handoff trace
        counter is cumulative like every trace counter)."""
        for k in ("handoffs", "partial_handoffs",
                  "kv_bytes_transferred", "requests_submitted",
                  "drain_truncations"):
            self.counters[k] = 0
        self._hand_stats = [0, 0.0, 0.0]
        self._t_first = self._t_last = None
        self._metrics_reset_t = self._clock()
        self._requests = [r for r in self._requests if not r.done]
        if self._flight is not None:
            self.counters.pop("collective_calls", None)
            self.counters.pop("collective_bytes", None)
        if self._obs is not None:
            self._obs.reset_window()
        self.prefill.reset_metrics()
        self.decode.reset_metrics()
        if self._obs is not None:
            # the workers' reset_window() replaced their histogram
            # objects — re-share the request-level set so both feed the
            # engine-level distributions again
            self._share_histograms()

    def _share_histograms(self):
        """Point both workers' request-level latency histograms at the
        engine-level objects: a request admits on the prefill group and
        finishes on the decode group (or on the prefill group for an
        EOS-at-first-token), and its TTFT/TPOT/queue-wait must land in
        ONE distribution wherever it completes."""
        for name in _SHARED_HISTOGRAMS:
            h = self._obs.registry.histogram(name)
            self.prefill._obs.registry.histograms[name] = h
            self.decode._obs.registry.histograms[name] = h

    @property
    def observability(self) -> Optional[Observability]:
        return self._obs

    def _require_obs(self) -> Observability:
        if self._obs is None:
            raise RuntimeError(
                "observability is disabled for this engine; construct "
                "with DisaggregatedEngine(..., observability=True)")
        return self._obs

    def export_trace(self, path: str) -> str:
        from ..observability.roofline import roofline_chrome_events
        return self._require_obs().export_chrome(
            path, process_name="paddle_tpu disagg serving",
            extra_events=roofline_chrome_events(
                self.decode._roofline_metrics()))

    def write_timeline(self, path: str) -> str:
        return self._require_obs().write_jsonl(
            path, header={"mode": "serving",
                          "disaggregated": True,
                          "capacity": self.capacity,
                          "prefill_slots": self.prefill_slots,
                          "block_size": self.block_size,
                          "roofline":
                              self.decode._roofline_metrics()})

    # -- static program audit -----------------------------------------
    def program_specs(self, register: bool = True):
        """Both groups' programs under disagg names — the decode
        group's decode step, the prefill group's per-bucket prefill
        (plus COW page copier with a prefix cache), and the two handoff
        programs — so the PR-5 audit gate covers the disaggregated
        path next to (not instead of) the colocated programs."""
        from ..analysis import ProgramSpec, REGISTRY
        sds = jax.ShapeDtypeStruct
        specs = []
        for s in self.decode.program_specs(register=False):
            if s.name.startswith("serving_decode"):
                specs.append(dataclasses.replace(
                    s, name="disagg_decode",
                    tags=s.tags + ("disagg",)))
        for s in self.prefill.program_specs(register=False):
            if "prefill" in s.name:
                P = s.name.rsplit("_", 1)[1]
                specs.append(dataclasses.replace(
                    s, name=f"disagg_prefill_{P}",
                    tags=s.tags + ("disagg",)))
            elif "page_copy" in s.name:
                specs.append(dataclasses.replace(
                    s, name="disagg_page_copy",
                    tags=s.tags + ("disagg",)))
            elif "kv_spill" in s.name or "kv_restore" in s.name:
                # the prefill group's host-tier handoff pair
                specs.append(dataclasses.replace(
                    s, name="disagg_" + s.name[len("serving_"):],
                    tags=s.tags + ("disagg",)))
        # fresh jit instances for the handoff pair (auditing must not
        # disturb the live programs' caches)
        ext, ins = self._build_handoff_fns()
        pre_pools = jax.ShapeDtypeStruct(self.prefill._k_pools.shape,
                                         self.prefill._k_pools.dtype)
        dec_pools = jax.ShapeDtypeStruct(self.decode._k_pools.shape,
                                         self.decode._k_pools.dtype)
        W = self._xfer_w
        pages_sd = sds((pre_pools.shape[0], W) + pre_pools.shape[2:],
                       pre_pools.dtype)
        idx_sd = sds((W,), jnp.int32)
        specs.append(ProgramSpec(
            name="disagg_kv_extract", fn=ext,
            args=(pre_pools, pre_pools, idx_sd),
            tags=("serving", "disagg")))
        specs.append(ProgramSpec(
            name="disagg_kv_insert", fn=ins,
            args=(dec_pools, dec_pools, idx_sd, pages_sd, pages_sd),
            donate_argnums=(0, 1), carry={0: 0, 1: 1},
            tags=("serving", "disagg")))
        if register:
            for s in specs:
                REGISTRY.register(s)
        return specs

    def audit(self, register: bool = True):
        """Static audit of every program of both groups (trace-only;
        the trace counters the tier-1 suite pins are snapshotted and
        restored)."""
        from ..analysis import audit_spec as _audit, publish_findings
        import copy
        snaps = []
        for eng in (self.prefill, self.decode):
            snaps.append((eng.counters,
                          {k: copy.deepcopy(eng.counters[k])
                           for k in ("decode_traces", "prefill_traces",
                                     "calibration_traces",
                                     "offload_traces")}))
        h_snap = self.counters["handoff_traces"]
        try:
            reports = [_audit(s)
                       for s in self.program_specs(register=register)]
        finally:
            for counters, snap in snaps:
                counters.update(snap)
            self.counters["handoff_traces"] = h_snap
        publish_findings(reports, counters=self.counters, obs=self._obs)
        return reports
