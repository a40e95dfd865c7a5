"""hloscan infrastructure: artifacts, findings, waivers, stable IDs.

mxlint's unit of analysis is a source *file*; hloscan's is an
*artifact* — one captured program (jaxpr + lowered HLO + optimized HLO)
for one real entry point, plus the **contract** that entry point
declares (expected collective counts, dtype policy, sharding promises).
Rules read the artifact and emit findings where the compiled program
breaks the contract.

Finding IDs are stable across unrelated edits the same way mxlint's
are: they hash ``rule|artifact|key`` where ``key`` is derived from the
offending instruction's opcode + layout-free shape + ordinal among
same-shaped ops — never the instruction's numeric suffix or channel
id, which XLA renumbers on every recompile (see
:func:`tools.hloscan.hlo.stable_key`).

Waivers cannot live inline (HLO text is generated, not authored), so
they are declared on the artifact's contract::

    "waivers": [
        {"rule": "dtype-cliff", "match": "convert[f32]",
         "reason": "loss is accumulated in f32 by design"},
    ]

``reason`` is REQUIRED — a reasonless waiver is itself a ``bad-waiver``
finding, exactly as in mxlint.  ``match`` (optional) restricts the
waiver to findings whose key contains the substring; without it the
waiver covers every finding of that rule on that artifact.
"""
from __future__ import annotations

import hashlib
import os
from dataclasses import dataclass, field

from . import hlo

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: Contract keys understood by the shipped rules (checked so a typo'd
#: contract fails loudly instead of silently waiving a rule).
KNOWN_CONTRACT_KEYS = frozenset({
    "expect_overlap",          # collective-overlap: require hideable compute
    "allow_host_roundtrip",    # no-host-roundtrip: opt OUT of the rule
    "dtype_policy",            # dtype-cliff: "bf16" | None
    "resharding_free",         # resharding-detector: no data-movement colls
    "allowed_reshard_ops",     # ...except these base opcodes
    "expected_collectives",    # launch-count: {"all-reduce": 4} or int
    "collectives_stage",       # launch-count: count in "lowered" (the
                               # program's own psums) not the optimized HLO
    "collective_free",         # launch-count: require zero collectives
    "waivers",
})


@dataclass
class Finding:
    rule: str
    artifact: str        # artifact name, e.g. "fused_train_step.dp"
    key: str             # stable instruction key or rule-defined anchor
    message: str
    where: str = ""      # human hint: computation/instruction name
    id: str = ""
    waived: bool = False
    waive_reason: str | None = None
    baselined: bool = False

    def to_json(self):
        return {
            "id": self.id,
            "rule": self.rule,
            "artifact": self.artifact,
            "key": self.key,
            "where": self.where,
            "message": self.message,
            "waived": self.waived,
            "waive_reason": self.waive_reason,
            "baselined": self.baselined,
        }


@dataclass
class Artifact:
    """One captured program.  ``jaxpr``/``lowered``/``optimized`` are the
    raw texts (any may be None when that stage is unavailable); parsed
    modules are cached on first access."""
    name: str
    kind: str                       # train_step|allreduce|kernel|serve|fixture
    jaxpr: str | None = None
    lowered: str | None = None
    optimized: str | None = None
    contract: dict = field(default_factory=dict)
    meta: dict = field(default_factory=dict)
    _mods: dict = field(default_factory=dict, repr=False)

    def __post_init__(self):
        unknown = set(self.contract) - KNOWN_CONTRACT_KEYS
        if unknown:
            raise ValueError(
                f"artifact {self.name!r}: unknown contract key(s) "
                f"{sorted(unknown)} — known: {sorted(KNOWN_CONTRACT_KEYS)}")

    def module(self, stage):
        """Parsed :class:`hlo.Module` for ``stage`` in
        {"lowered", "optimized"}; None when the text is absent."""
        if stage not in self._mods:
            text = getattr(self, stage)
            self._mods[stage] = hlo.parse(text) if text else None
        return self._mods[stage]

    @property
    def best_module(self):
        """Optimized module when captured, else lowered — rules that care
        about *presence* of ops (host round-trip, resharding) read
        whichever is closest to what runs."""
        return self.module("optimized") or self.module("lowered")

    def finding(self, rule, key, message, where=""):
        return Finding(rule=rule, artifact=self.name, key=key,
                       message=message, where=where)

    def keyed(self, rule, instr, ordinal, message, where=""):
        """Finding anchored on one instruction via its stable key."""
        return self.finding(rule, hlo.stable_key(instr, ordinal), message,
                            where=where or instr.name)


def assign_ids(findings):
    """Stable IDs: sha1-12 of ``rule|artifact|key``, disambiguated by
    occurrence order for identical triples."""
    seen = {}
    for f in findings:
        key = f"{f.rule}|{f.artifact}|{f.key}"
        n = seen.get(key, 0)
        seen[key] = n + 1
        if n:
            key = f"{key}|#{n + 1}"
        f.id = hashlib.sha1(key.encode("utf-8")).hexdigest()[:12]
    return findings


def apply_waivers(findings, artifact):
    """Mark findings covered by the artifact's contract waivers; emit a
    ``bad-waiver`` finding per waiver missing its reason."""
    waivers = artifact.contract.get("waivers", ())
    out = []
    for f in findings:
        for w in waivers:
            if w.get("rule") != f.rule or not w.get("reason"):
                continue
            match = w.get("match")
            if match and match not in f.key:
                continue
            f.waived, f.waive_reason = True, w["reason"]
            break
        out.append(f)
    for i, w in enumerate(waivers):
        if not w.get("reason"):
            out.append(Finding(
                rule="bad-waiver", artifact=artifact.name,
                key=f"waiver[{i}]:{w.get('rule', '?')}",
                message="contract waiver without a reason — add "
                        '"reason": "<why the compiled program is allowed '
                        'to do this>" (unreasoned waivers hide intent)'))
    return out


def ordinal_keys(instructions):
    """Pair each instruction with its ordinal among same-(opcode, shape)
    peers — the disambiguator :func:`hlo.stable_key` expects."""
    counts = {}
    out = []
    for instr in instructions:
        k = (instr.opcode, instr.clean_shape)
        n = counts.get(k, 0)
        counts[k] = n + 1
        out.append((instr, n))
    return out
