"""A registry of the programs this process compiled, so that every
device operation of a trace can be put down to the program and the
``jax.named_scope`` that issued it.

A device trace names an operation by its instruction's text WITHOUT its
metadata (``%fusion.121 = bf16[32,4096]{...} fusion(...)``); the
compiled program's text has the same instruction WITH it
(``metadata={op_name="jit(step)/while/body/closed_call/layer/qkv/dot_general"}``).
Only the process that compiled the program holds both, so the carrier
lives here, inside the program:

- ``note(fn, args)``: the owner of a jitted program (the engine for its
  decode program and each bucket's chunk program, the ``Trainer`` for
  its step) calls it at the program's first dispatch UNDER A PROFILER
  SESSION (``spans.tracing()``) and at no other time, with the live
  arguments, before the call that donates them. By then the program
  has run: ``fn.lower(*args)`` is the trace and ``.compile()`` the
  executable that ``jit``'s own caches hold, so nothing is traced or
  compiled again. What is kept is the ``jax.stages.Compiled``: no
  weights, no pools, no engine. With no session nothing of this module
  runs: a dispatch pays the owner's "noted" test and one
  ``TraceAnnotation.is_enabled()``, and set-up pays nothing (PR 40
  noted every program at its first dispatch, and the benchmark's
  ``setup_s`` rose by 1.3-4.6 s a cell on the chip).
- ``scopes()``: asked by a READER, never by a step. Each noted program
  is parsed once, on the first call after it was noted: the module's
  name as the trace prints it (``jit_step``) and ``{instruction name:
  scope}``. The ``Compiled`` is dropped then. A program that no
  dispatch ran under a session is not noted: the trace holds none of
  its operations either.

**What a scope is.** An instruction's ``op_name`` with what JAX wraps
around the program's own ``jax.named_scope`` names taken off:
``jit(...)`` segments, the transformations ``jvp(...)`` /
``transpose(...)`` (a backward operation keeps its forward's scope:
``transpose(jvp(forward))/...`` reads ``forward``), the words of control
flow (``while/body/closed_call``, ``cond``, ``checkpoint``, ...) and the
primitive's own last word. ``jit(step)/while/body/closed_call/layer/qkv/
dot_general`` reads ``layer/qkv``. **A fusion takes its own ``op_name``,
which XLA sets from the fusion's root; where it has none, the root's.**
A fusion that XLA made of operations from two scopes is therefore put
down whole to the scope of its root. What the COMPILER made (a copy, a
re-layout: no ``op_name``) takes the scope of the first instruction
that reads it, else of the first it reads, else of the loop it runs in
(``Program.made`` says which). ``resolve(scope)`` gives the innermost
name of ``PROGRAM_SCOPES`` in a scope (``layers/layer/qkv`` ->
``layer/qkv``), or None.

**Joining an execution of a trace to its program.** The trace's
``XLA Modules`` line names an execution ``jit_chunk(<number>)``; every
bucket's chunk program is a ``jit_chunk`` and ``fusion.158`` is another
instruction in each. The number is the device runtime's own (looked at
on a v5e, PR 40: 64 bits that are no part of the executable's
``fingerprint`` and not in its serialized module), so
``ProgramRegistry.find`` joins by content: an execution belongs to the
noted program whose instructions hold every operation seen inside it,
name AND result type (the text up to the opcode), the latter because
two buckets' programs number their instructions alike.

**A stale cache.** JAX's persistent compilation cache keys a program
WITHOUT its metadata: an entry written before a scope was added or
renamed gives ``as_text()`` the old scopes. Clear the cache directory
after touching a scope; the benchmark's ``scope_coverage_pct.thr``
reads low where that was forgotten.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, Iterable, List, Optional

__all__ = ["PROGRAM_SCOPES", "Program", "ProgramRegistry", "REGISTRY",
           "note", "scopes", "scope_of", "resolve",
           "instruction_head"]

# what a layer DOES, not what implements it: a PR that replaces an
# implementation keeps the name, and the benchmark's readers with it
PROGRAM_SCOPES = (
    # the decode program (jit_step) and the chunk programs (jit_chunk)
    "embed",             # token embedding (and its multiplier)
    "layers",            # a loop over layers itself: counter, carried values
    "kv_gather",         # the reference chunk: a request's pages as a dense view
    "kv_scatter",        # ... and the view written back through the write table
    "layer/qkv",         # input norm, q/k/v projections, rotary
    "layer/kv_write",    # the new keys and values into the cache
    "layer/attention",   # attention over the cache (the paged launch; a chunk's)
    "layer/attn_out",    # output projection, residual (and its all-reduce)
    "layer/mixer_in",    # a recurrent layer: norm, in_proj, convolution
    "layer/mixer_out",   # ... its gate, norm, out_proj, residual
    "layer/mlp",         # post norm + MLP (the launch, XLA's, or the shared MLP)
    "layer/router",      # an expert layer's norm, router, routing counts
    "moe_experts",       # sort, gather, both grouped products, unsort
    "ssm_update",        # the one-token state update
    "ssd_scan",          # the chunked scan
    "head",              # final norm, logits
    "sample",            # key split, sampling, the lengths' increment
    # the training program (jit_step_fn)
    "forward",           # the model up to the final hidden states (+ backward)
    "loss",              # the fused head + cross-entropy (+ backward)
    "optimizer/grads",   # the gradient into the form the pass reads
    "optimizer/clip",    # the global norm and the clip scale
    "optimizer/update",  # the pass over the state
    "optimizer/params_out",  # the new parameters out of the pass's outputs
)

# segments of an op_name that are JAX's, not the program's
_STRUCTURE = frozenset((
    "while", "body", "cond", "closed_call", "core_call", "checkpoint",
    "remat", "rematted_computation", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "custom_lin", "shard_map", "pjit", "xla_call",
    "named_call", "scan", "switch"))
_BRANCH = re.compile(r"^branch_\d+_fun$")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_FUNCTIONS = frozenset(("jit", "pjit", "xla_call"))   # jit(<a function's name>)

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = (.*)$")
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_CALLS = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
_STEPS = re.compile(r"\b(?:body|condition|true_computation|false_computation)"
                    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
# instructions that do no work of their own, and those that only pass a
# value on
_NO_WORK = frozenset(("parameter", "constant", "tuple", "get-tuple-element",
                      "while", "conditional", "call", "after-all",
                      "partition-id", "replica-id"))
_PASSES = frozenset(("get-tuple-element", "bitcast", "tuple", "copy",
                     "copy-start", "copy-done", "reshape", "transpose",
                     "convert", "slice", "dynamic-slice"))
_HANDS_ON = frozenset(("tuple", "get-tuple-element", "bitcast"))


_SCOPE_SET = frozenset(PROGRAM_SCOPES)


def _split(op_name: str) -> List[str]:
    """An ``op_name`` at the slashes outside parentheses."""
    out, depth, cur = [], 0, []
    for ch in op_name:
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
            continue
        depth += ch == "("
        depth -= ch == ")"
        cur.append(ch)
    out.append("".join(cur))
    return out


def scope_of(op_name: str) -> Optional[str]:
    """``jit(step)/while/body/closed_call/layer/qkv/dot_general`` ->
    ``layer/qkv``; None where nothing of the program's own is left (or
    the name is no path at all: a parameter's)."""
    parts = _split(op_name)
    if len(parts) < 2:
        return None
    kept = []
    for seg in parts[:-1]:               # the last word is the primitive
        while True:
            m = _WRAPPED.match(seg)
            if m is None:
                break
            if m.group(1) in _FUNCTIONS:
                seg = ""
                break
            seg = m.group(2)             # jvp(x), transpose(jvp(x)) -> x
        for word in _split(seg):
            if word and word not in _STRUCTURE and not _BRANCH.match(word):
                kept.append(word)
    return "/".join(kept) or None


def resolve(scope: Optional[str]) -> Optional[str]:
    """The innermost name of ``PROGRAM_SCOPES`` in ``scope``:
    ``layers/layer/qkv`` is ``layer/qkv``, ``layers`` itself ``layers``,
    ``forward/flash`` is ``forward``."""
    words = scope.split("/") if scope else []
    for i in reversed(range(len(words))):
        for name in ("/".join(words[max(i - 1, 0):i + 1]), words[i]):
            if name in _SCOPE_SET:
                return name
    return None


def instruction_head(text: str) -> str:
    """``%fusion.6 = bf16[...]...`` (a trace's event name, a line of a
    program's text) -> ``fusion.6``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def _typed(rest: str) -> str:
    """The result type of an instruction: its text right of `` = `` up
    to the opcode (what tells two buckets' ``fusion.6`` apart)."""
    m = _OPCODE.search(" " + rest)
    return rest[:m.start(1) - 1].strip() if m else rest.strip()


@dataclasses.dataclass
class Program:
    """One compiled program as a reader needs it. ``name``: the module's
    name as the trace prints it before the parenthesis (``jit_step``).
    ``scopes``: {instruction name: scope or None} for every instruction
    that can appear as an operation of the trace (entry, loop bodies and
    conditions, branches; not the inside of a fusion). ``types``:
    {instruction name: its result type's text}; ``opcodes``: {instruction
    name: opcode}. ``made``: {instruction name: the instruction it took
    its scope from}, for what the COMPILER made (a copy, a re-layout, a
    slice: no ``op_name``): such an instruction goes to the scope of the
    first instruction that reads it, else of the first it reads, else of
    the loop it runs in.
    ``key``: what ``note`` returned for it."""
    name: str
    scopes: Dict[str, Optional[str]]
    types: Dict[str, str]
    opcodes: Dict[str, str]
    made: Dict[str, str] = dataclasses.field(default_factory=dict)
    key: int = -1

    @classmethod
    def from_text(cls, text: str, key: int = -1) -> "Program":
        """Parse a compiled module's ``as_text()``."""
        name, entry, cur, cur_name = "", None, None, None
        comps, roots = {}, {}     # computation -> [row]; -> its ROOT row
        for line in text.splitlines():
            if not name:
                m = _MODULE.match(line)
                if m:
                    name = m.group(1)
                continue
            if cur is None:
                m = _COMPUTATION.match(line)
                if m:
                    cur_name = m.group(2)
                    cur = comps.setdefault(cur_name, [])
                    if m.group(1):
                        entry = cur_name
                continue
            if line.startswith("}"):
                cur = None
                continue
            m = _INSTRUCTION.match(line)
            if m is None:
                continue
            body, _, meta = m.group(3).partition(", metadata={")
            op = _OP_NAME.search(meta)
            opcode = _OPCODE.search(" " + body)
            row = _Row(m.group(2), _typed(body),
                       opcode.group(1) if opcode else "",
                       scope_of(op.group(1)) if op else None, body)
            cur.append(row)
            if m.group(1):
                roots[cur_name] = row

        def root_scope(comp, depth=0):
            row = roots.get(comp)
            if row is None or depth > 8:
                return None
            if row.scope is not None:
                return row.scope
            called = _CALLS.findall(row.body)
            return root_scope(called[0], depth + 1) if called else None

        # the computations whose instructions run as operations of their
        # own: the entry and what it reaches through loops and branches
        # ... each with the scope of the instruction that runs it (a
        # loop's ``while``), for what nothing else names
        steps, todo = {}, [(entry, None)] if entry else []
        while todo:
            c, owner = todo.pop()
            if c in steps or c not in comps:
                continue
            steps[c] = owner
            for row in comps[c]:
                inner = _STEPS.findall(row.body)
                for group in _BRANCHES.findall(row.body):
                    inner.extend(g.strip().lstrip("%")
                                 for g in group.split(","))
                if row.opcode == "call":
                    inner.extend(_CALLS.findall(row.body))
                todo.extend((i, row.scope or owner) for i in inner)
        out = cls(name, {}, {}, {}, {}, key)
        for c, owner in steps.items():
            rows = comps[c]
            for row in rows:
                if row.scope is None and row.opcode == "fusion":
                    called = _CALLS.findall(row.body)
                    row.scope = root_scope(called[0]) if called else None
            out._inherit(rows, owner)
            for row in rows:
                out.scopes[row.head] = row.scope
                out.types[row.head] = row.typed
                out.opcodes[row.head] = row.opcode
        return out

    def _inherit(self, rows, owner):
        """What the compiler made takes the scope of its first reader
        (the rows are in the schedule's order: readers come later), else
        of the first instruction it reads, through what only passes a
        value on (a tuple's element, a bitcast); else ``owner``, the
        scope of the loop whose body this is."""
        by_head = {r.head: r for r in rows}
        reads = {r.head: [o for o in _OPERAND.findall(
            r.body.partition("(")[2]) if o in by_head] for r in rows}
        readers = {}
        for r in rows:
            for o in reads[r.head]:
                readers.setdefault(o, []).append(r)

        def reader(head, depth=0):
            for u in readers.get(head, ()):
                if u.scope is None and u.opcode in _HANDS_ON and depth < 4:
                    u = reader(u.head, depth + 1)
                if u is not None and u.scope is not None:
                    return u
            return None

        for r in reversed(rows):
            if r.scope is None and r.opcode not in _NO_WORK:
                src = reader(r.head)
                if src is not None:
                    r.scope, self.made[r.head] = src.scope, src.head
        for r in rows:
            if r.scope is None and r.opcode not in _NO_WORK:
                todo, seen = list(reads[r.head]), set()
                while todo:
                    o = by_head[todo.pop(0)]
                    if o.head in seen:
                        continue
                    seen.add(o.head)
                    if o.scope is not None:
                        r.scope, self.made[r.head] = o.scope, o.head
                        break
                    if o.opcode in _PASSES:
                        todo.extend(reads[o.head])
                if r.scope is None and owner is not None:
                    r.scope, self.made[r.head] = owner, "(its loop)"

    def holds(self, operations: Iterable[str]) -> bool:
        """Whether every operation (a trace's event names) is an
        instruction of this program, by name and by result type."""
        for text in operations:
            head, rest = instruction_head(text), text.partition(" = ")[2]
            if head not in self.types or (
                    rest and not rest.lstrip().startswith(self.types[head])):
                return False
        return True

    def scope(self, operation: str) -> Optional[str]:
        """The scope of one operation of the trace (None: unnamed, or no
        instruction of this program)."""
        return self.scopes.get(instruction_head(operation))


@dataclasses.dataclass
class _Row:
    """One instruction while a text is parsed."""
    head: str
    typed: str
    opcode: str
    scope: Optional[str]
    body: str


class ProgramRegistry:
    """The compiled programs of one process, the newest ``keep``: a
    process that builds engine after engine (the tests) would otherwise
    hold every executable it ever compiled."""

    def __init__(self, keep: int = 32):
        self._noted = collections.OrderedDict()   # key -> Compiled | Program
        self._keep = keep
        self._next = 0

    def note(self, fn, args) -> int:
        """Keep ``fn``'s compiled program for ``args`` (see the module's
        text: call it at the first dispatch under a profiler session,
        before the call that donates ``args``). An owner that dispatches
        a ``jax.stages.Compiled`` of its own (the observed ``Trainer``)
        hands that, and it is kept as it is. Returns the key
        ``scopes(keys)`` finds it by; -1, and nothing is kept, for a
        callable that is no jitted function (a test's stand-in): it has
        no compiled text."""
        if hasattr(fn, "as_text"):
            return self._put(fn)
        if not hasattr(fn, "lower"):
            return -1
        return self._put(fn.lower(*args).compile())

    def _put(self, item) -> int:
        key, self._next = self._next, self._next + 1
        self._noted[key] = item
        while len(self._noted) > self._keep:
            self._noted.popitem(last=False)
        return key

    def add_text(self, text: str) -> int:
        """A program from its text alone (tests; a text saved earlier)."""
        key = self._put(None)
        self._noted[key] = Program.from_text(text, key)
        return key

    def scopes(self, keys: Optional[Iterable[int]] = None) -> List[Program]:
        """The noted programs, parsed (each once); with ``keys`` those of
        one owner."""
        wanted = None if keys is None else set(keys)
        out = []
        for key, item in list(self._noted.items()):
            if wanted is not None and key not in wanted:
                continue
            if not isinstance(item, Program):
                item = self._noted[key] = Program.from_text(
                    item.as_text(), key)
            out.append(item)
        return out

    def find(self, module: str, operations: Iterable[str]
             ) -> Optional[Program]:
        """The noted program an execution belongs to: ``module`` is the
        execution's name in the trace (``jit_chunk(123)``),
        ``operations`` the event names seen inside it. The newest that
        holds them all (an engine built again compiles the same text)."""
        name = module.split("(", 1)[0]
        operations = list(operations)
        for prog in reversed(self.scopes()):
            if prog.name == name and prog.holds(operations):
                return prog
        return None

    def clear(self):
        self._noted.clear()


REGISTRY = ProgramRegistry()


def note(fn, args) -> int:
    """``REGISTRY.note``: what the owners of the jitted programs call."""
    return REGISTRY.note(fn, args)


def scopes(keys: Optional[Iterable[int]] = None) -> List[Program]:
    """``REGISTRY.scopes``: what a reader calls."""
    return REGISTRY.scopes(keys)
