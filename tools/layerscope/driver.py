"""layerscope driver: capture, census, fence, report.

Exit status mirrors hloscan/mxlint: 0 when every finding is waived or
baselined AND the baseline is not stale, 1 when a live finding remains
or the baseline names findings that no longer exist, 2 on usage error.
The checked-in baseline (``tools/layerscope_baseline.json``) is EMPTY:
the known offenders (ResNet stem, BN-backward — VERDICT items 3/6) are
waived on the contract with reasons, so the census *documents* them;
the baseline exists for genuinely new debt, and stale entries FAIL.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_BASELINE = os.path.join(REPO_ROOT, "tools",
                                "layerscope_baseline.json")
RESULTS_DIR = os.path.join(REPO_ROOT, "benchmark", "results")

JSON_SCHEMA_VERSION = 1

#: Every rule the census contract can emit, for the verdict lines.
RULES = ("attribution-coverage", "mfu-floor", "stale-floor",
         "stale-waiver", "bad-waiver")


def finding_id(entry, f):
    """Stable ID for a census finding (sha1-12 of tool|rule|entry|key,
    same recipe as hloscan/mxlint)."""
    blob = f"layerscope|{f['rule']}|{entry}|{f['key']}"
    return hashlib.sha1(blob.encode()).hexdigest()[:12]


def census_docs(names=None, device=None):
    """Run the census over ``names`` (default: every census entry
    point).  Imports jax and compiles — the heavy step."""
    from mxnet_tpu.analysis import census
    kw = {} if device is None else {"device": device}
    names = census.census_entrypoint_names() if not names else list(names)
    return [census.census_one(n, **kw) for n in names]


# --------------------------------------------------------------------------
# reporting
# --------------------------------------------------------------------------
def _fmt_flops(v):
    for unit, div in (("GF", 1e9), ("MF", 1e6), ("kF", 1e3)):
        if v >= div:
            return f"{v / div:.1f}{unit}"
    return f"{v:.0f}F"


def render_table(doc, out=None):
    """The per-layer census table.  Cost-model mode shows modeled %
    step time and speed-of-light MFU; measured mode adds achieved
    TF/s / GB/s / MFU."""
    lines = []
    measured = doc["mode"] == "measured"
    head = (f"layerscope: {doc['entry']} [{doc['device']}, {doc['mode']}] "
            f"— {doc['attributed_flops_fraction']:.1%} of "
            f"{_fmt_flops(doc['totals']['flops'])} attributed")
    lines.append(head)
    cols = f"{'layer':<34} {'ph':<3} {'%time':>6} {'flops':>8} " \
           f"{'intens':>7} {'SOL-MFU':>8}"
    if measured:
        cols += f" {'TF/s':>7} {'GB/s':>7} {'MFU':>7}"
    cols += "  bound"
    lines.append(cols)
    waived_by_key = {f["key"]: f for f in doc["findings"]
                     if f["waived"]}
    for row in doc["rows"]:
        key = f"{row['layer']}@{row['phase']}"
        mark = " [waived]" if key in waived_by_key else ""
        line = (f"{row['layer'][:34]:<34} {row['phase']:<3} "
                f"{row['pct_time']:>5.1f}% "
                f"{_fmt_flops(row['flops']):>8} "
                f"{'-' if row['intensity'] is None else format(row['intensity'], '.1f'):>7} "
                f"{row['mfu_sol']:>7.1%}")
        if measured:
            tf = row["tf_per_s"]
            line += (f" {'-' if tf is None else format(tf, '.2f'):>7}"
                     f" {'-' if row['gb_per_s'] is None else format(row['gb_per_s'], '.1f'):>7}"
                     f" {'-' if row['mfu'] is None else format(row['mfu'], '.1%'):>7}")
        line += f"  {row['bound']}{mark}"
        lines.append(line)
    text = "\n".join(lines) + "\n"
    if out is not None:
        out.write(text)
    return text


def top_sag(doc, n=5):
    """Top-``n`` layers by % of step time with their bound class — the
    ``layer_census_top_sag`` summary."""
    rows = [r for r in doc["rows"]][:n]
    return [f"{r['layer']}@{r['phase']} {r['pct_time']:.1f}% {r['bound']}"
            for r in rows]


def verdict_lines(docs, baselined_ids=()):
    """Per-rule ``layerscope <rule> PASS|FAIL`` lines (beside hloscan's
    in the dryrun rider)."""
    live = {}
    for doc in docs:
        for f in doc["findings"]:
            if f["waived"]:
                continue
            if finding_id(doc["entry"], f) in baselined_ids:
                continue
            live[f["rule"]] = live.get(f["rule"], 0) + 1
    lines = []
    for rule in RULES:
        n = live.get(rule, 0)
        verdict = "PASS" if not n else f"FAIL ({n})"
        lines.append(f"layerscope {rule:22s} {verdict}  "
                     f"[{len(docs)} entries]")
    return lines


# --------------------------------------------------------------------------
# baseline (hloscan policy: empty by default, stale entries FAIL)
# --------------------------------------------------------------------------
def load_baseline(path):
    if not os.path.exists(path):
        return {}
    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    return data.get("findings", {})


def write_baseline(path, docs):
    entries = {}
    for doc in docs:
        for f in doc["findings"]:
            if f["waived"]:
                continue
            entries[finding_id(doc["entry"], f)] = {
                "rule": f["rule"], "entry": doc["entry"], "key": f["key"],
                "message": f["message"]}
    payload = {
        "comment": "layerscope grandfathered findings — entries are debts, "
                   "not permissions; known offenders belong on the contract "
                   "as reasoned waivers instead. Stale entries FAIL the "
                   "census. Regenerate with `python -m tools.layerscope "
                   "--update-baseline`.",
        "version": JSON_SCHEMA_VERSION,
        "findings": dict(sorted(entries.items())),
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2, sort_keys=False)
        f.write("\n")
    return entries


# --------------------------------------------------------------------------
# pipeline
# --------------------------------------------------------------------------
def artifact_path(entry):
    return os.path.join(RESULTS_DIR, f"layer_census_{entry}.json")


def write_artifact(doc, path=None):
    from mxnet_tpu.analysis import census
    path = path or artifact_path(doc["entry"])
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        f.write(census.dumps(doc))
        f.write("\n")
    return path


def run(names=None, device=None, baseline_path=None,
        update_baseline=False, fmt="text", verdicts=False, metrics=True,
        artifacts=True, docs=None, out=sys.stdout):
    """Full pipeline; returns the process exit code."""
    if docs is None:
        docs = census_docs(names, device=device)
    docs = list(docs)
    baseline = load_baseline(baseline_path) if baseline_path else {}
    if update_baseline:
        if not baseline_path:
            out.write("layerscope: --update-baseline needs --baseline "
                      "PATH\n")
            return 2
        entries = write_baseline(baseline_path, docs)
        out.write(f"layerscope: baseline written — {len(entries)} entr"
                  f"{'y' if len(entries) == 1 else 'ies'} -> "
                  f"{baseline_path}\n")
        return 0

    present, live = set(), []
    for doc in docs:
        for f in doc["findings"]:
            if f["waived"]:
                continue
            fid = finding_id(doc["entry"], f)
            present.add(fid)
            if fid not in baseline:
                live.append((doc["entry"], fid, f))
    stale_ids = set(baseline) - present

    written = []
    if artifacts:
        written = [write_artifact(doc) for doc in docs]
    if metrics:
        try:
            from mxnet_tpu.analysis import census
            for doc in docs:
                census.publish_metrics(doc)
        except Exception:  # mxlint: disable=swallowed-exception -- metrics mirroring is best-effort; the report itself still prints below
            pass

    if fmt == "json":
        payload = {
            "version": JSON_SCHEMA_VERSION,
            "tool": "layerscope",
            "entries": [{"entry": d["entry"], "mode": d["mode"],
                         "attributed_flops_fraction":
                             d["attributed_flops_fraction"],
                         "top_sag": top_sag(d),
                         "findings": d["findings"]} for d in docs],
            "artifacts": written,
            "stale_baseline_ids": sorted(stale_ids),
            "summary": {
                "entries": len(docs),
                "live": len(live),
                "waived": sum(1 for d in docs for f in d["findings"]
                              if f["waived"]),
                "stale_baseline": len(stale_ids),
            },
        }
        json.dump(payload, out, indent=2)
        out.write("\n")
    else:
        for doc in docs:
            render_table(doc, out=out)
            out.write("layer_census_top_sag: " +
                      "; ".join(top_sag(doc)) + "\n")
            for f in doc["findings"]:
                if f["waived"]:
                    out.write(f"  waived [{f['rule']}] {f['key']}: "
                              f"{f['reason']}\n")
        for entry, fid, f in live:
            out.write(f"{entry}: [{f['rule']}] {f['message']}  "
                      f"(id {fid})\n")
        if stale_ids:
            out.write(f"layerscope: FAIL — {len(stale_ids)} stale "
                      f"baseline entr"
                      f"{'y' if len(stale_ids) == 1 else 'ies'}; prune "
                      f"with --update-baseline: "
                      f"{', '.join(sorted(stale_ids))}\n")
        verdict = "clean" if not live else \
            f"{len(live)} live finding{'s' if len(live) != 1 else ''}"
        out.write(f"layerscope: {verdict} — {len(docs)} entries"
                  + (f", artifacts: {', '.join(written)}" if written
                     else "") + "\n")
    if verdicts:
        for line in verdict_lines(docs, baselined_ids=set(baseline)):
            out.write(line + "\n")
    return 1 if (live or stale_ids) else 0


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="python -m tools.layerscope",
        description="Per-layer speed-of-light census with roofline "
                    "attribution (docs/OBSERVABILITY.md, 'Layer "
                    "census').")
    p.add_argument("--entry", action="append", dest="entries",
                   metavar="NAME",
                   help="census entry point (repeatable; default: all — "
                        "see --list-entries)")
    p.add_argument("--device", default=None,
                   help="roofline peaks to classify against "
                        "(default: tpu-v5e)")
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--baseline", default=DEFAULT_BASELINE,
                   help="baseline JSON of grandfathered finding IDs "
                        "(default: tools/layerscope_baseline.json)")
    p.add_argument("--no-baseline", action="store_true",
                   help="ignore the baseline (report everything)")
    p.add_argument("--update-baseline", action="store_true",
                   help="rewrite the baseline from current findings")
    p.add_argument("--verdicts", action="store_true",
                   help="append per-rule PASS/FAIL verdict lines")
    p.add_argument("--no-metrics", action="store_true",
                   help="skip publishing mxtpu_layer_mfu gauges")
    p.add_argument("--no-artifact", action="store_true",
                   help="skip writing benchmark/results/"
                        "layer_census_<entry>.json")
    p.add_argument("--list-entries", action="store_true")
    args = p.parse_args(argv)

    if args.list_entries:
        from mxnet_tpu.analysis import census_entrypoint_names
        for name in census_entrypoint_names():
            print(name)
        return 0

    return run(names=args.entries or None, device=args.device,
               baseline_path=None if args.no_baseline else args.baseline,
               update_baseline=args.update_baseline,
               fmt=args.format, verdicts=args.verdicts,
               metrics=not args.no_metrics,
               artifacts=not args.no_artifact)


if __name__ == "__main__":
    sys.exit(main())
