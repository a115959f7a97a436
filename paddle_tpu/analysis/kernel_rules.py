"""Kernel-geometry rule passes over captured Pallas launches.

PR 5's jaxpr auditor gates program-level bug classes; the serving and
training hot paths now live one layer down, inside the Pallas
megakernels, where the recurring review-caught bugs are GEOMETRY bugs:
a non-divisor tile whose floor-divided grid silently drops the trailing
columns, a pipeline window set that overshoots the scoped-VMEM OOM
point, an output index map that revisits a block nobody declared as an
accumulator. Every kernel routes its ``pl.pallas_call`` through
``ops/pallas/_util.audited_pallas_call``, which records a
:class:`~paddle_tpu.ops.pallas._util.KernelLaunchSpec` at trace time;
the rules here evaluate the captured index maps CONCRETELY over the
full grid (they are pure Python on ints — scalar-prefetch maps are
evaluated against zero-filled sample tables, recorded as ``sampled`` in
the finding detail) and prove:

- ``GRID_FLOOR_DROP``   — an operand's block-coordinate set does not
  cover every block of its array: output elements never written, or —
  for launches WITHOUT scalar prefetch, where every read is statically
  addressed — input blocks never read (the fused_mlp_block non-divisor
  ``block_f`` review class: ``grid=(F // bf,)`` leaves the trailing
  weight columns out of the accumulation). Scalar-prefetch launches
  read pages data-dependently (live pages only), so their input
  coverage is intentionally partial and exempt.
- ``OOB_BLOCK``         — an index map sends a block start past the
  array extent (or negative) on some grid step; a partially overhanging
  LAST block is legal (Pallas masks it) and not flagged.
- ``WRITE_RACE``        — an output index map is non-injective across
  grid steps without a declared accumulation (``accum_outputs``):
  sequential TPU grids make revisits well-defined, but an UNDECLARED
  revisit is a last-write-wins bug waiting for a grid reorder.
- ``VMEM_OVERCOMMIT``   — Σ block bytes × pipeline-window count
  (grid-varying blocks are double-buffered by Mosaic, constant-index
  blocks are fetched once, scratch is resident) over the scoped-VMEM
  envelope — the PR-7 residual-epilogue OOM class.
- ``SCRATCH_MISMATCH``  — the kernel callable's positional arity does
  not match prefetch + inputs + outputs + scratch (or a zero-sized
  scratch buffer is declared).
- ``DISPATCH_KEY_GAP``  — the registry lint: a meta key read by a
  variant's ``supports()`` (or the candidate builders it calls) that
  the op's declared program-cache/autotune key coverage
  (``KERNELS.declare_cache_key``) does not include — the thrice-fixed
  ``_PAGED_CACHE`` stale-route class.

Findings reuse the PR-5 frozen schema (:class:`.rules.Finding`), so the
baseline-diff workflow, fingerprints and the CLI/JSON contract are
shared with the program auditor.
"""
from __future__ import annotations

import inspect
import itertools
import os
from collections.abc import Mapping
from typing import Dict, List, Optional, Tuple

import numpy as np

from .rules import Finding

__all__ = ["KERNEL_RULE_CODES", "check_launch", "dispatch_key_rule",
           "scoped_vmem_envelope", "modeled_launch_bytes"]

KERNEL_RULE_CODES = ("GRID_FLOOR_DROP", "OOB_BLOCK", "WRITE_RACE",
                     "VMEM_OVERCOMMIT", "SCRATCH_MISMATCH",
                     "DISPATCH_KEY_GAP")

#: the documented v5e scoped-VMEM OOM point the PR-6/7 review rounds
#: kept bumping into; a launch whose pipelined windows exceed it fails
#: to compile (or OOMs) on real chips
SCOPED_VMEM_BYTES = 16 << 20


def scoped_vmem_envelope(budget: int = 0) -> int:
    """The VMEM ceiling a launch's windows must fit: the scoped-VMEM
    window (``PADDLE_TPU_SCOPED_VMEM_BUDGET``, default 16 MiB), raised
    to the fused dispatch budget (``PADDLE_TPU_FUSED_VMEM_BUDGET``,
    captured per launch) when an operator explicitly configures a
    larger one — the dispatch budget bounds the weight-resident share,
    the envelope bounds weights + double-buffered pipeline windows +
    scratch together."""
    env = int(os.environ.get("PADDLE_TPU_SCOPED_VMEM_BUDGET",
                             SCOPED_VMEM_BYTES))
    return max(env, int(budget or 0))


# -- geometry evaluation ------------------------------------------------


def _itemsize(dtype: str) -> int:
    import jax.numpy as jnp

    try:
        return int(jnp.dtype(dtype).itemsize)
    except TypeError:
        return 4


def _norm_block(block_shape) -> Tuple[int, ...]:
    """Block shape with squeezed (None) dims as size-1."""
    return tuple(1 if b is None else int(b) for b in block_shape)


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


#: operand / scratch spaces that take no part of the VMEM window: SMEM
#: scalars, DMA semaphores, and an input left in HBM (``pl.ANY``) that
#: the kernel copies out of for itself, through VMEM scratch it declares
_NOT_VMEM = ("smem", "semaphore", "any")


class _ClampedTable:
    """ndarray stand-in whose ``__getitem__`` clamps every integer
    index component into the array's extent. The ``full`` prefetch
    sample (below) fills sequence lengths with huge values so a
    length-clamped page walk (the fused prefill attention kernel's
    ``page_index``: ``idx = min(step, (len-1)//BS)``) advances a FRESH table entry per grid
    step instead of collapsing onto entry 0 — but that same huge
    length lets the computed table index run past the table extent on
    ragged last steps, which would IndexError on a bare ndarray. The
    clamp keeps the dereference legal without changing the property
    being probed (does the fetched coordinate CHANGE step to step)."""

    def __init__(self, arr):
        self._arr = arr
        self.shape = arr.shape
        self.dtype = arr.dtype

    def __getitem__(self, idx):
        if not isinstance(idx, tuple):
            idx = (idx,)
        clamped = tuple(
            min(max(int(i), 0), self._arr.shape[d] - 1)
            for d, i in enumerate(idx))
        return self._arr[clamped]


def _prefetch_samples(spec, ramp: bool = False,
                      full: bool = False) -> List:
    """Stand-ins for the scalar-prefetch operands. The default is
    zero-filled: a zero table is always a VALID table (page 0 exists
    whenever the pool is non-empty), so bounds proven on it are proofs
    for the in-range-table contract, recorded as ``sampled`` in the
    finding detail. ``ramp=True`` fills ints with ``arange % 2``
    instead — used ONLY by the VMEM window model to detect that a
    table-dereferencing index map actually VARIES across grid steps
    (on the all-zero table every page fetch collapses to page 0 and a
    streamed, double-buffered operand would masquerade as a resident
    constant block); {0, 1} stays in range for any table whose target
    extent is >= 2, and the ramp is never used for bounds findings.
    ``full=True`` fills ints with ``arange + 2**20`` wrapped in a
    :class:`_ClampedTable` — used ONLY by the bytes model: huge
    sequence lengths defeat the length clamp in data-dependent page
    maps so every grid step walks a fresh table entry (the
    max-traffic table), and distinct table entries make each fetch a
    distinct page. Never used for bounds findings either."""
    out = []
    for shape, dtype in spec.prefetch:
        try:
            dt = np.dtype(dtype)
        except TypeError:
            dt = np.int32
        if full and np.issubdtype(dt, np.integer):
            n = int(np.prod(shape or (1,), dtype=np.int64))
            arr = (np.arange(n, dtype=np.int64)
                   + (1 << 20)).astype(dt).reshape(shape)
            out.append(_ClampedTable(arr))
        elif ramp and np.issubdtype(dt, np.integer):
            n = int(np.prod(shape or (1,), dtype=np.int64))
            out.append((np.arange(n, dtype=dt) % 2).reshape(shape))
        else:
            out.append(np.zeros(shape, dt))
    return out


def _operand_coords(spec, op, _memo=None, ramp: bool = False,
                    full: bool = False) -> Optional[Dict[Tuple, Tuple]]:
    """grid point -> block coordinates for one operand, evaluated
    concretely over the FULL grid. None for whole-array operands
    (memory-space specs: no index map, no blocking). ``_memo`` (keyed
    by operand identity) dedupes the evaluation across rules — one
    walk of the grid per operand, not one per rule."""
    if op.block_shape is None or op.index_map is None:
        return None
    key = (id(op), ramp, full)
    if _memo is not None and key in _memo:
        return _memo[key]
    samples = _prefetch_samples(spec, ramp=ramp, full=full)
    coords: Dict[Tuple, Tuple] = {}
    for point in itertools.product(*(range(g) for g in spec.grid)):
        # np.int32 grid indices: the all-int32 index maps (e.g. the
        # clamped page fetch) call .astype on them, which a bare
        # python int lacks
        raw = op.index_map(*(np.int32(p) for p in point), *samples)
        if not isinstance(raw, tuple):
            raw = (raw,)
        coords[point] = tuple(int(v) for v in raw)
    if _memo is not None:
        _memo[key] = coords
    return coords


def _finding(program, code, severity, site, message, detail):
    return Finding(rule="kernel_geometry", code=code, severity=severity,
                   program=program, site=site, message=message,
                   detail=detail)


def _bounds_findings(spec, program, label, op, coords) -> List[Finding]:
    """OOB_BLOCK for one operand: any block whose START lies outside
    the array extent. A ragged LAST block overhanging the extent is
    legal (Pallas masks the tail) and not flagged."""
    out = []
    block = _norm_block(op.block_shape)
    if not coords:
        return out
    ndim = len(op.shape)
    for point, coord in coords.items():
        if len(coord) != ndim or len(block) != ndim:
            out.append(_finding(
                program, "OOB_BLOCK", "error",
                f"{spec.name}/{label}",
                f"{spec.name} {label}: index map returns {len(coord)} "
                f"coords for a {ndim}-d array {list(op.shape)}",
                {"kernel": spec.name, "grid_point": list(point),
                 "coords": list(coord)}))
            return out
        for d, (c, bs, ext) in enumerate(zip(coord, block, op.shape)):
            start = c * bs
            if start < 0 or start >= ext:
                out.append(_finding(
                    program, "OOB_BLOCK", "error",
                    f"{spec.name}/{label}",
                    (f"{spec.name} {label}: grid point {list(point)} "
                     f"maps dim {d} to block {c} (elements "
                     f"[{start}, {start + bs})) outside the array "
                     f"extent {ext} — the fetch/write is past the "
                     "array"),
                    {"kernel": spec.name, "grid_point": list(point),
                     "dim": d, "block_index": c, "block_size": bs,
                     "extent": ext,
                     "sampled": spec.num_scalar_prefetch > 0}))
                return out  # one proof per operand is enough
    return out


def _coverage_finding(spec, program, label, op, coords, verb):
    block = _norm_block(op.block_shape)
    covered = set(coords.values())
    required = set(itertools.product(
        *(range(_cdiv(ext, bs)) for ext, bs in zip(op.shape, block))))
    missing = required - covered
    if not missing:
        return None
    first = sorted(missing)[0]
    starts = [c * bs for c, bs in zip(first, block)]
    return _finding(
        program, "GRID_FLOOR_DROP", "error",
        f"{spec.name}/{label}",
        (f"{spec.name} {label}: {len(missing)} of {len(required)} "
         f"blocks are never {verb} (first missing block {list(first)} "
         f"= elements starting at {starts} of {list(op.shape)}) — a "
         "floor-divided grid is dropping the trailing blocks (the "
         "non-divisor block_f class)"),
        {"kernel": spec.name, "missing_blocks": len(missing),
         "required_blocks": len(required),
         "first_missing": list(first), "grid": list(spec.grid),
         "block_shape": list(block)})


def _output_findings(spec, program, memo) -> List[Finding]:
    """Coverage + injectivity + bounds for every output."""
    out: List[Finding] = []
    for i, op in enumerate(spec.outputs):
        label = f"out{i}"
        coords = _operand_coords(spec, op, memo)
        if coords is None:
            continue  # whole-array output: trivially covered
        out.extend(_bounds_findings(spec, program, label, op, coords))
        block = _norm_block(op.block_shape)
        if len(block) != len(op.shape) or any(
                len(c) != len(block) for c in coords.values()):
            continue  # malformed arity: already an OOB_BLOCK finding —
            # comparing wrong-arity coords would fabricate coverage/
            # race findings on top of the real one
        # an output aliased to an input keeps that input's contents in
        # every block the grid does not write (an in-place update of a
        # part of a pool): only a fresh output has to be covered
        aliased = i in set((spec.input_output_aliases or {}).values())
        f = None if aliased else _coverage_finding(
            spec, program, label, op, coords, "written")
        if f is not None:
            out.append(f)
        covered = set(coords.values())
        if len(covered) < len(coords) and i not in spec.accum_outputs:
            revisits = len(coords) - len(covered)
            out.append(_finding(
                program, "WRITE_RACE", "error",
                f"{spec.name}/{label}",
                (f"{spec.name} {label}: index map revisits the same "
                 f"output block on {revisits} of {len(coords)} grid "
                 "steps with no declared accumulation — sequential "
                 "last-write-wins today, a race after any grid "
                 "reorder; declare it via audited_pallas_call("
                 "accum_outputs=...) if the revisit is an intentional "
                 "scratch-accumulate pattern"),
                {"kernel": spec.name, "revisited_steps": revisits,
                 "grid_steps": len(coords),
                 "distinct_blocks": len(covered)}))
    return out


def _input_findings(spec, program, memo) -> List[Finding]:
    out: List[Finding] = []
    for i, op in enumerate(spec.inputs):
        coords = _operand_coords(spec, op, memo)
        if coords is None:
            continue
        out.extend(_bounds_findings(spec, program, f"in{i}", op, coords))
        if spec.num_scalar_prefetch:
            continue  # page reads are data-dependent: live pages only
        block = _norm_block(op.block_shape)
        if len(block) != len(op.shape) or any(
                len(c) != len(block) for c in coords.values()):
            continue  # malformed arity: OOB_BLOCK already reported
        f = _coverage_finding(spec, program, f"in{i}", op, coords,
                              "read")
        if f is not None:
            out.append(f)
    return out


def _vmem_findings(spec, program, memo) -> List[Finding]:
    """Window model: a grid-VARYING block is double-buffered by the
    Mosaic pipeline (2 windows), a constant-index block is fetched once
    and stays resident (1 window — revisit elision), scratch is
    resident for the whole launch. SMEM operands don't charge the
    window. Variance of a table-dereferencing (scalar-prefetch) map is
    probed on BOTH the zero and the ramp sample tables — on the
    all-zero table every page fetch collapses to page 0 and a streamed
    pool operand would wrongly look like a resident constant. Σ must
    fit the scoped-VMEM envelope.

    Combined multi-window launches (resident weights + streamed tiles
    or pages in ONE grid, as the fused prefill attention kernel) are
    additionally held to the dispatch-budget side of the
    :func:`scoped_vmem_envelope` contract: the RESIDENT share alone
    (1-window operands + scratch — what stays in VMEM for the whole
    launch, unpipelined by construction) must fit the per-launch
    dispatch budget, so a kernel cannot satisfy the envelope by
    streaming its tiles while its resident set already exceeds what
    its supports() predicate budgeted for weights. A launch with no
    streamed operand keeps the historic contract — it is wholly
    resident and the envelope alone bounds it."""
    need = 0
    resident = 0
    streams = False
    parts = []
    for kind, ops in (("in", spec.inputs), ("out", spec.outputs)):
        for i, op in enumerate(ops):
            if op.space in _NOT_VMEM:
                continue
            if op.block_shape is None:
                nbytes = int(np.prod(op.shape or (1,), dtype=np.int64)) \
                    * _itemsize(op.dtype)
                windows = 1
            else:
                block = _norm_block(op.block_shape)
                nbytes = int(np.prod(block, dtype=np.int64)) \
                    * _itemsize(op.dtype)
                coords = _operand_coords(spec, op, memo)
                distinct = set(coords.values()) if coords else set()
                if spec.num_scalar_prefetch and len(distinct) <= 1:
                    ramped = _operand_coords(spec, op, memo, ramp=True)
                    if ramped:
                        distinct |= set(ramped.values())
                windows = 2 if len(distinct) > 1 else 1
            need += windows * nbytes
            if windows == 1:
                resident += nbytes
            else:
                streams = True
            if windows * nbytes >= (64 << 10):
                parts.append(f"{kind}{i}:{windows}x{nbytes >> 10}KiB")
    for shape, dtype, space in spec.scratch:
        if space in _NOT_VMEM:
            continue
        sbytes = int(np.prod(shape or (1,), dtype=np.int64)) \
            * _itemsize(dtype)
        need += sbytes
        resident += sbytes
    out: List[Finding] = []
    envelope = scoped_vmem_envelope(spec.vmem_budget)
    if need > envelope:
        out.append(_finding(
            program, "VMEM_OVERCOMMIT", "error",
            f"{spec.name}/windows",
            (f"{spec.name}: pipelined VMEM windows total "
             f"~{need >> 20}MiB > the {envelope >> 20}MiB scoped-VMEM "
             f"envelope (largest: {', '.join(parts[:4])}) — the "
             "double-buffered window set OOMs a v5e (the PR-7 "
             "residual-epilogue class); shrink the block sizes or "
             "scale the per-buffer budget by the window count"),
            {"kernel": spec.name, "need_bytes": need,
             "envelope_bytes": envelope,
             "fused_budget_bytes": spec.vmem_budget,
             "windows": parts}))
    if streams and spec.vmem_budget and resident > int(spec.vmem_budget):
        # the dispatch-budget half of the envelope contract: the
        # resident share (constant-index operands + scratch — held for
        # the WHOLE launch, so pipelining cannot hide it) must fit the
        # budget the kernel's supports() predicate dispatched against
        out.append(_finding(
            program, "VMEM_OVERCOMMIT", "error",
            f"{spec.name}/resident",
            (f"{spec.name}: resident VMEM share (constant windows + "
             f"scratch) totals ~{resident >> 20}MiB > the "
             f"{int(spec.vmem_budget) >> 20}MiB per-launch dispatch "
             "budget — the launch-long resident set exceeds what the "
             "dispatch predicate budgeted; stream the oversized "
             "operand or shrink the resident tiles"),
            {"kernel": spec.name, "resident_bytes": resident,
             "fused_budget_bytes": spec.vmem_budget,
             "windows": parts}))
    return out


# -- HBM traffic model (roofline numerator) -----------------------------


def _transition_count(coords) -> int:
    """Block fetches for one operand under Mosaic's revisit elision:
    one for the first grid step plus one per consecutive-step
    coordinate CHANGE. ``coords`` preserves the ``itertools.product``
    walk order, which is the sequential TPU grid order, so a block
    that only changes on the outer grid dim is charged once per outer
    step — exactly the pipeline's refetch behaviour. A constant-index
    (resident) operand degenerates to 1."""
    it = iter(coords.values())
    try:
        prev = next(it)
    except StopIteration:
        return 1
    n = 1
    for c in it:
        if c != prev:
            n += 1
            prev = c
    return n


def _operand_fetches(spec, op, memo) -> Optional[int]:
    """Modeled HBM block fetches for one operand, or None for a
    whole-array operand. Static maps are counted on the zero sample;
    data-dependent (scalar-prefetch-dereferencing) maps are ALSO
    probed on the ``full`` clamped sample — huge lengths + distinct
    table entries — and the max taken, because on the zero sample a
    page walk collapses onto page 0 and would masquerade as resident
    (the same failure mode the VMEM window model's ramp re-probe
    guards against, but here the 0/1 ramp still underestimates: the
    model must charge one fetch per DISTINCT page, not per parity
    flip)."""
    coords = _operand_coords(spec, op, memo)
    if coords is None:
        return None
    fetches = _transition_count(coords)
    if spec.num_scalar_prefetch:
        full = _operand_coords(spec, op, memo, full=True)
        if full:
            fetches = max(fetches, _transition_count(full))
    return fetches


def modeled_launch_bytes(spec, memo: Optional[Dict] = None) -> Dict:
    """Modeled HBM traffic for one captured launch.

    The same window walk the ``VMEM_OVERCOMMIT`` rule does, summed
    over the full grid instead of maxed over one step: every blocked
    operand is charged ``block_bytes ×`` its :func:`_operand_fetches`
    transition count (streamed operands pay once per revisit-elided
    refetch, resident constant-index operands pay exactly once),
    whole-array operands are charged their array bytes once — except
    one the kernel fetches from for itself (``any`` space), which is
    charged what the launch declares it copies (``fetched_bytes``, on
    the ``full`` prefetch sample: every page live) — and SMEM
    operands and scratch charge nothing (scalars / VMEM-only). The
    model deliberately ignores accumulator read-modify-write traffic
    (revisited output blocks stay in VMEM between visits — that is
    what ``accum_outputs`` declares) and assumes a perfect pipeline
    (no redundant refetch of an unchanged window).

    Returns ``{"total_bytes", "read_bytes", "written_bytes",
    "operands": [{"operand", "fetches", "bytes"} ...]}``.
    """
    if memo is None:
        memo = {}
    read = written = 0
    detail = []
    for kind, ops in (("in", spec.inputs), ("out", spec.outputs)):
        for i, op in enumerate(ops):
            if op.space == "smem":
                continue
            fetches = None if op.fetched_bytes is not None \
                else _operand_fetches(spec, op, memo)
            if op.fetched_bytes is not None:
                # self-fetched (``any`` space): no blocks to count; the
                # launch's own declaration, on the max-traffic table
                nbytes = int(op.fetched_bytes(
                    *_prefetch_samples(spec, full=True)))
            elif fetches is None:
                fetches = 1
                nbytes = int(np.prod(op.shape or (1,),
                                     dtype=np.int64)) \
                    * _itemsize(op.dtype)
            else:
                block = _norm_block(op.block_shape)
                nbytes = fetches \
                    * int(np.prod(block, dtype=np.int64)) \
                    * _itemsize(op.dtype)
            if kind == "in":
                read += nbytes
            else:
                written += nbytes
            detail.append({"operand": f"{kind}{i}",
                           "fetches": fetches, "bytes": nbytes})
    return {"total_bytes": read + written, "read_bytes": read,
            "written_bytes": written, "operands": detail}


def _scratch_findings(spec, program) -> List[Finding]:
    out: List[Finding] = []
    for i, (shape, dtype, space) in enumerate(spec.scratch):
        if int(np.prod(shape or (1,), dtype=np.int64)) == 0:
            out.append(_finding(
                program, "SCRATCH_MISMATCH", "error",
                f"{spec.name}/scratch{i}",
                f"{spec.name}: scratch {i} has zero elements "
                f"({list(shape)}) — a dead declaration",
                {"kernel": spec.name, "scratch": i,
                 "shape": list(shape)}))
    if spec.kernel is None:
        return out
    try:
        sig = inspect.signature(spec.kernel)
    except (TypeError, ValueError):
        return out
    params = list(sig.parameters.values())
    has_var = any(p.kind is inspect.Parameter.VAR_POSITIONAL
                  for p in params)
    npos = sum(1 for p in params
               if p.kind in (inspect.Parameter.POSITIONAL_ONLY,
                             inspect.Parameter.POSITIONAL_OR_KEYWORD)
               and p.default is inspect.Parameter.empty)
    expected = (spec.num_scalar_prefetch + len(spec.inputs)
                + len(spec.outputs) + len(spec.scratch))
    bad = (npos > expected) if has_var else (npos != expected)
    if bad:
        out.append(_finding(
            program, "SCRATCH_MISMATCH", "error",
            f"{spec.name}/arity",
            (f"{spec.name}: kernel takes {npos} positional refs"
             f"{' (+ *varargs)' if has_var else ''} but the launch "
             f"passes {expected} ({spec.num_scalar_prefetch} prefetch "
             f"+ {len(spec.inputs)} in + {len(spec.outputs)} out + "
             f"{len(spec.scratch)} scratch) — the ref lists are "
             "misaligned"),
            {"kernel": spec.name, "positional": npos,
             "expected": expected, "varargs": has_var}))
    return out


def check_launch(spec, program: str = None) -> List[Finding]:
    """Run every geometry rule over one captured launch. ``program``
    names the audited shape class (defaults to the kernel name) and
    keys the finding fingerprints."""
    program = program or spec.name
    memo: Dict[int, Dict] = {}
    out: List[Finding] = []
    out.extend(_output_findings(spec, program, memo))
    out.extend(_input_findings(spec, program, memo))
    out.extend(_vmem_findings(spec, program, memo))
    out.extend(_scratch_findings(spec, program))
    return out


# -- registry lint ------------------------------------------------------


class _RecordingMeta(Mapping):
    """Mapping recording every key a supports() predicate (or anything
    it calls) reads — the instrumentation behind DISPATCH_KEY_GAP.
    Membership tests count as reads, and any iteration or copy
    (``keys``/``items``/``values``/``dict(meta)``/``{**meta}``)
    conservatively counts as reading EVERY key — a predicate that
    copies or walks the meta can depend on all of it. Deliberately NOT
    a dict subclass: CPython's ``dict(subclass)`` C fast path skips
    overridden methods, while copying a Mapping goes through the
    (instrumented) protocol."""

    def __init__(self, data):
        self._data = dict(data)
        self.accessed = set()

    def __getitem__(self, k):
        self.accessed.add(k)
        return self._data[k]

    def get(self, k, default=None):
        self.accessed.add(k)
        return self._data.get(k, default)

    def __contains__(self, k):
        self.accessed.add(k)
        return k in self._data

    def __iter__(self):
        self.accessed.update(self._data)
        return iter(self._data)

    def __len__(self):
        return len(self._data)


def dispatch_key_rule(registry, op: str, meta: Dict,
                      program: str = "kernel_registry") -> List[Finding]:
    """Instrument every variant's ``supports(meta)`` for op and flag
    meta keys it reads that the op's declared program-cache/autotune
    key coverage (``registry.declare_cache_key``) does not include.

    A supports() read is a TRACE-TIME dispatch input: if the caller's
    program cache does not key on it, a changed value silently replays
    a program compiled under the other routing — the bug class fixed
    three times by hand in the ``_PAGED_CACHE`` route key before this
    lint existed."""
    out: List[Finding] = []
    decl = registry.cache_key_decl(op)
    if decl is None:
        out.append(_finding(
            program, "DISPATCH_KEY_GAP", "error", f"{op}:undeclared",
            (f"kernel op {op!r} has supports() dispatch but no "
             "declare_cache_key() coverage declaration — the lint "
             "cannot prove its callers' program caches key every "
             "dispatch input"),
            {"op": op}))
        return out
    fields, covers = decl
    fieldset = set(fields)
    for variant in registry.variants(op):
        if variant.supports is None:
            continue
        rec = _RecordingMeta(meta)
        try:
            variant.supports(rec)
        except Exception as e:  # noqa: BLE001 — a raising predicate is a bug
            out.append(_finding(
                program, "DISPATCH_KEY_GAP", "error",
                f"{op}/{variant.name}:raised",
                f"supports() of {op}/{variant.name} raised "
                f"{type(e).__name__}: {e}",
                {"op": op, "variant": variant.name,
                 "exception": type(e).__name__}))
            continue
        gap = sorted(k for k in rec.accessed
                     if k not in fieldset
                     and covers.get(k) not in fieldset)
        if gap:
            out.append(_finding(
                program, "DISPATCH_KEY_GAP", "error",
                f"{op}/{variant.name}",
                (f"supports() of {op}/{variant.name} reads meta "
                 f"key(s) {gap} that the op's declared program-cache/"
                 "autotune key coverage does not include — a changed "
                 "value would flip dispatch without retracing (the "
                 "_PAGED_CACHE stale-route class); add the key to the "
                 "caller's cache key and to declare_cache_key()"),
                {"op": op, "variant": variant.name, "gap": gap,
                 "accessed": sorted(rec.accessed),
                 "declared": sorted(fieldset)}))
    return out
