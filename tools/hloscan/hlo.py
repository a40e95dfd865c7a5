"""Lightweight parser + dependence analysis for XLA HLO *text*.

hloscan's rules read the artifact XLA actually runs, so the input is the
textual HLO the toolchain prints — both forms:

* **unoptimized** (``lowered.compiler_ir(dialect="hlo").as_hlo_text()``):
  instruction names without ``%``, operands as bare names — this is the
  user program as lowered, before any compiler pass (the right layer for
  dtype intent: the optimizer is allowed to upcast);
* **optimized/scheduled** (``compiled.as_text()``): ``%``-prefixed names,
  typed operands, ``is_scheduled=true`` — the instruction order of the
  entry computation IS the schedule the backend executes.

This is deliberately NOT a full HLO grammar: it recovers what the rules
need — per-computation instruction lists in schedule order, opcodes,
result dtypes/shapes, operand edges (the dependence graph), attribute
text — and stays robust to the attribute soup (metadata, layouts,
sharding annotations) by keeping it as raw text with regex accessors.

Async-collective modeling
-------------------------
On TPU the compiler splits collectives into ``all-reduce-start`` /
``all-reduce-done`` pairs and the latency-hiding scheduler moves real
compute between them.  The CPU backend this repo's CI runs on keeps
collectives synchronous in HLO (the async split happens below HLO, in
the thunk runtime), so :func:`overlap_report` covers both shapes:

* literal ``*-start``/``*-done`` pairs → the compute *actually
  scheduled* strictly between them;
* synchronous collectives → the compute an async scheduler *may* place
  in the start→done window, which is exactly the set of ops neither
  upstream (producers must finish before start) nor downstream
  (consumers must wait for done) of the collective in the dependence
  graph.  Zero such ops means no scheduler on any backend can overlap
  this collective — the dependence structure, not the toolchain, forbids
  it.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

# --------------------------------------------------------------------------
# opcode classes
# --------------------------------------------------------------------------
#: Cross-device collectives (base opcodes; async forms append -start/-done).
COLLECTIVE_OPS = frozenset({
    "all-reduce", "all-gather", "all-to-all", "reduce-scatter",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
})

#: Collectives that move/reshape data rather than reduce it — the ones a
#: fully-specified sharding should never need (resharding-detector).
RESHARD_OPS = frozenset({
    "all-gather", "all-to-all", "collective-permute", "ragged-all-to-all",
})

#: Ops that cross the host boundary by construction.
HOST_OPS = frozenset({
    "infeed", "outfeed", "send", "send-done", "recv", "recv-done",
})

#: custom-call targets that reach back into the host Python process.
HOST_CALLBACK_TARGET_RE = re.compile(
    r"callback|host_callback|xla_ffi_python|HostExecute", re.IGNORECASE)

#: Pure data movement / bookkeeping — never "real compute" for overlap.
_NON_COMPUTE = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "bitcast-convert", "copy", "copy-start", "copy-done", "reshape",
    "transpose", "broadcast", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "convert", "iota", "after-all",
    "partition-id", "replica-id", "optimization-barrier", "domain", "pad",
    "reverse", "gather", "get-dimension-size", "set-dimension-size",
    "add-dependency", "tuple-select", "rng-get-and-update-state",
}) | COLLECTIVE_OPS | HOST_OPS | frozenset(
    op + "-start" for op in COLLECTIVE_OPS) | frozenset(
    op + "-done" for op in COLLECTIVE_OPS) | frozenset(
    {"async-start", "async-update", "async-done"})

_DTYPE_RE = re.compile(
    r"\b(pred|bf16|f8e\w+|f16|f32|f64|s4|s8|s16|s32|s64|"
    r"u4|u8|u16|u32|u64|c64|c128)\[")

_INSTR_RE = re.compile(
    r"^\s*(?P<root>ROOT\s+)?%?(?P<name>[A-Za-z_][\w.\-]*)\s*=\s*"
    r"(?P<shape>\([^)]*\)|[A-Za-z0-9_\[\],]+(?:\{[\d,]*\})?)\s+"
    r"(?P<op>[a-z][\w\-]*)\((?P<rest>.*)$")

_COMP_RE = re.compile(
    r"^\s*(?P<entry>ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*(\([^)]*\)\s*"
    r"->\s*[^{]+)?\{\s*$")

_CALLED_RE = re.compile(
    r"\b(?:to_apply|calls|condition|body|then_computation|else_computation|"
    r"called_computation)=%?([\w.\-]+)")

_TARGET_RE = re.compile(r'custom_call_target="([^"]*)"')


@dataclass(eq=False)   # identity semantics: usable in sets, one node per parse
class Instruction:
    name: str
    shape: str                 # raw result shape text, e.g. f32[8,4]{1,0}
    opcode: str
    operands: tuple            # operand instruction names (resolved later)
    attrs: str                 # raw attribute text after the operand list
    is_root: bool = False
    index: int = -1            # schedule position within its computation

    @property
    def result_dtypes(self):
        return tuple(m.group(1) for m in _DTYPE_RE.finditer(self.shape))

    @property
    def clean_shape(self):
        """Shape without layout braces — stable across layout assignment."""
        return re.sub(r"\{[\d,]*\}", "", self.shape).replace(" ", "")

    def attr(self, regex):
        m = re.search(regex, self.attrs)
        return m.group(1) if m else None

    @property
    def custom_call_target(self):
        m = _TARGET_RE.search(self.attrs)
        return m.group(1) if m else None

    def called_computations(self):
        return [m for m in _CALLED_RE.findall(self.attrs)]


@dataclass
class Computation:
    name: str
    is_entry: bool
    instructions: list = field(default_factory=list)
    by_name: dict = field(default_factory=dict)

    def consumers(self):
        """name -> list of instructions using it (built on demand)."""
        cons = {i.name: [] for i in self.instructions}
        for instr in self.instructions:
            for op in instr.operands:
                if op in cons:
                    cons[op].append(instr)
        return cons

    def ancestors(self, instr):
        """Transitive producers of ``instr`` (operand closure)."""
        seen, stack = set(), list(instr.operands)
        while stack:
            n = stack.pop()
            if n in seen or n not in self.by_name:
                continue
            seen.add(n)
            stack.extend(self.by_name[n].operands)
        return {self.by_name[n] for n in seen}

    def descendants(self, instr, cons=None):
        """Transitive consumers of ``instr``'s result."""
        cons = cons or self.consumers()
        seen, stack = set(), [instr.name]
        while stack:
            n = stack.pop()
            for user in cons.get(n, ()):
                if user.name not in seen:
                    seen.add(user.name)
                    stack.append(user.name)
        return {self.by_name[n] for n in seen}


@dataclass
class Module:
    name: str
    is_scheduled: bool
    num_partitions: int
    computations: dict = field(default_factory=dict)
    entry: Computation = None

    def all_instructions(self):
        for comp in self.computations.values():
            yield from comp.instructions


def _split_operands(args):
    """Top-level comma split of an operand list; each operand's *name* is
    its last ``%``-or-bare identifier (typed operands in optimized text,
    bare names in unoptimized text).  Non-name pieces (constant literals)
    yield nothing and are skipped at graph build via by_name lookup."""
    parts, depth, cur = [], 0, []
    for ch in args:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    names = []
    for p in parts:
        m = re.search(r"%?([A-Za-z_][\w.\-]*)\s*$", p.strip())
        if m:
            names.append(m.group(1))
    return tuple(names)


def _parse_instruction(line):
    m = _INSTR_RE.match(line)
    if not m:
        return None
    rest = m.group("rest")
    depth, cut = 1, len(rest)
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                cut = i
                break
    return Instruction(
        name=m.group("name"), shape=m.group("shape").strip(),
        opcode=m.group("op"), operands=_split_operands(rest[:cut]),
        attrs=rest[cut + 1:], is_root=bool(m.group("root")))


def parse(text):
    """Parse HLO text into a :class:`Module`.  Tolerant: unrecognized
    lines are skipped (attribute continuations, comments)."""
    lines = text.splitlines()
    header = next((ln for ln in lines if ln.startswith("HloModule")), "")
    mod = Module(
        name=(re.match(r"HloModule ([\w.\-]+)", header) or [None, "?"])[1]
        if header else "?",
        is_scheduled="is_scheduled=true" in header,
        num_partitions=int(
            (re.search(r"num_partitions=(\d+)", header) or [None, "1"])[1]),
    )
    comp = None
    for ln in lines:
        stripped = ln.strip()
        if comp is None:
            if stripped.endswith("{") and not stripped.startswith("HloModule"):
                m = _COMP_RE.match(ln)
                if m:
                    comp = Computation(name=m.group("name"),
                                       is_entry=bool(m.group("entry")))
            continue
        if stripped == "}" or stripped.startswith("} "):
            mod.computations[comp.name] = comp
            if comp.is_entry:
                mod.entry = comp
            comp = None
            continue
        instr = _parse_instruction(ln)
        if instr is not None:
            instr.index = len(comp.instructions)
            comp.instructions.append(instr)
            comp.by_name[instr.name] = instr
    if mod.entry is None and mod.computations:
        # single-computation modules without an ENTRY tag
        mod.entry = next(iter(mod.computations.values()))
    return mod


# --------------------------------------------------------------------------
# classification
# --------------------------------------------------------------------------
def base_collective(opcode):
    """'all-reduce-start' -> 'all-reduce'; None for non-collectives."""
    for suffix in ("-start", "-done"):
        if opcode.endswith(suffix):
            opcode = opcode[: -len(suffix)]
            break
    return opcode if opcode in COLLECTIVE_OPS else None


def is_collective_issue(instr):
    """A collective's *issue* op: the sync form or the -start half (the
    -done half is the same launch completing, never counted twice)."""
    base = base_collective(instr.opcode)
    return base is not None and not instr.opcode.endswith("-done")


def is_compute(instr):
    """Real work the scheduler can hide a collective behind: dots,
    convolutions, fusions, reductions, elementwise arithmetic, kernels —
    everything that is not pure data movement or bookkeeping."""
    return instr.opcode not in _NON_COMPUTE


def is_host_op(instr):
    if instr.opcode in HOST_OPS:
        return True
    if instr.opcode == "custom-call":
        target = instr.custom_call_target or ""
        return bool(HOST_CALLBACK_TARGET_RE.search(target))
    return False


# --------------------------------------------------------------------------
# collective-overlap modeling
# --------------------------------------------------------------------------
def overlap_report(comp):
    """Per collective issue in ``comp``: can real compute overlap it?

    Returns a list of dicts::

        {"instr": Instruction, "mode": "paired"|"modeled",
         "compute": [Instruction, ...],   # overlappable real compute
         "first_consumer": str|None}

    ``paired``: the module already carries ``*-start``/``*-done`` —
    compute is what sits strictly between them in the schedule (the
    scheduler's actual decision).  ``modeled``: the collective is
    synchronous in HLO — compute is every op independent of it in the
    dependence graph (neither ancestor nor descendant), i.e. what an
    async split + latency-hiding schedule is free to move into the
    start→done window.
    """
    cons = comp.consumers()
    out = []
    done_for = {}
    for instr in comp.instructions:
        if base_collective(instr.opcode) and instr.opcode.endswith("-done"):
            for op in instr.operands:
                done_for[op] = instr
    for instr in comp.instructions:
        if not is_collective_issue(instr):
            continue
        users = cons.get(instr.name, [])
        first_consumer = min(users, key=lambda u: u.index).name if users \
            else None
        if instr.opcode.endswith("-start"):
            done = done_for.get(instr.name)
            hi = done.index if done is not None else len(comp.instructions)
            compute = [i for i in comp.instructions
                       if instr.index < i.index < hi and is_compute(i)]
            out.append({"instr": instr, "mode": "paired",
                        "compute": compute,
                        "first_consumer": done.name if done else None})
        else:
            blocked = comp.ancestors(instr) | comp.descendants(instr, cons)
            blocked.add(instr)
            compute = [i for i in comp.instructions
                       if i not in blocked and is_compute(i)]
            out.append({"instr": instr, "mode": "modeled",
                        "compute": compute,
                        "first_consumer": first_consumer})
    return out


def collective_counts(module, entry_only=False):
    """Issue-count per base collective opcode (starts counted, dones not)."""
    counts = {}
    comps = [module.entry] if (entry_only and module.entry) \
        else list(module.computations.values())
    for comp in comps:
        for instr in comp.instructions:
            if is_collective_issue(instr):
                base = base_collective(instr.opcode)
                counts[base] = counts.get(base, 0) + 1
    return counts


def stable_key(instr, ordinal):
    """Finding-key fragment for one instruction that survives unrelated
    edits: opcode + layout-free shape + ordinal among same-keyed ops —
    never the instruction's numeric suffix or channel id, which renumber
    on any recompile."""
    return f"{instr.opcode}{instr.clean_shape}#{ordinal}"
