"""launch-count: the collective census matches the bucketed contract.

Ancestor claim (PR 4 headline): bucketing collapsed the dp gradient
path from one collective per parameter (160 for the resnet50 profile)
to one per bucket.  That collapse is trivially easy to regress — a
bucketer bypass on an unusual dtype, a cache-key bug that splits
buckets, a refactor that re-introduces per-key launches — and the only
place the truth lives is the compiled module's opcode census.

The contract pins it::

    "expected_collectives": {"all-reduce": 4}     # exact per-opcode
    "expected_collectives": 4                     # exact total
    "collective_free": true                       # zero collectives
    "collectives_stage": "lowered"                # where to count

Where to count: by default the optimized module, the only place the
collectives exist that the SPMD partitioner derives from shardings (a
`FusedTrainStep(mesh=...)`).  There XLA's combiner has already merged
them, so the number is also the compiler's and moves with the toolchain.
A program that spells its collectives itself (`shard_map` + `psum`, one
per bucket) declares ``"collectives_stage": "lowered"`` and is counted
before any XLA pass: that census is the bucket plan's own.

Counting convention: *issues*, not instructions — a ``-start``/``-done``
pair is one launch (the start is counted, the done is the same launch
completing).  Counts cover every computation in the module, so
collectives inside while-loop bodies are not hidden.  Both a shortfall
and an excess are findings: fewer collectives than declared means the
contract is stale or a collective was traced away (a silently
non-synchronizing step), more means launches leaked back in.
"""
from __future__ import annotations

from .. import hlo
from . import Rule


class LaunchCount(Rule):
    name = "launch-count"
    description = ("collective issue count per step differs from the "
                   "bucketed contract (PR 4's 160->4 collapse)")

    def check(self, artifact):
        expected = artifact.contract.get("expected_collectives")
        collective_free = artifact.contract.get("collective_free")
        if expected is None and not collective_free:
            return
        stage = artifact.contract.get("collectives_stage")
        mod = artifact.module(stage) if stage else artifact.best_module
        if mod is None:
            yield artifact.finding(
                self.name, "no-module",
                "launch-count contract declared but no HLO captured for "
                "this artifact — capture layer broken")
            return
        counts = hlo.collective_counts(mod)
        total = sum(counts.values())
        if collective_free:
            if total:
                census = ", ".join(f"{k}={v}" for k, v in sorted(
                    counts.items()))
                yield artifact.finding(
                    self.name, "collective-free",
                    f"collective_free program issues {total} collective(s) "
                    f"({census}) — a single-device/replicated artifact "
                    f"should compile to zero cross-device traffic")
            return
        if isinstance(expected, dict):
            for op in sorted(set(expected) | set(counts)):
                want, got = expected.get(op, 0), counts.get(op, 0)
                if want == got:
                    continue
                direction = "leaked back in" if got > want else \
                    "were traced away (step may silently not synchronize)"
                yield artifact.finding(
                    self.name, f"count:{op}",
                    f"`{op}` issue count {got} != contract {want}: "
                    f"launches {direction} — recount the bucket plan or "
                    f"update the contract with the change that moved it")
        else:
            if total != int(expected):
                census = ", ".join(f"{k}={v}" for k, v in sorted(
                    counts.items())) or "none"
                direction = "leaked back in" if total > int(expected) else \
                    "were traced away (step may silently not synchronize)"
                yield artifact.finding(
                    self.name, "count:total",
                    f"total collective issues {total} != contract "
                    f"{expected} ({census}): launches {direction}")
