"""What must hold of a benchmark manifest, as functions that take one, so that
the same checks run on BENCHMARK.json and on a rehearsed addition to it
(`test_chipbench_harness.py::test_an_addition_takes_new_files_and_appends`).

Each check states an invariant and never a length or a frozen list: a
configuration, a cell or a metric is added by new files and by appending to
lists (chipbench/README.md), and none of these checks may need an edit for it."""
import os
import re

from chipbench import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SETUP_LAYER = "set-up (gluon/block.py, gluon/fused_step.py, telemetry/watchdog.py)"
OPS_LAYER = "ops and kernels (ops/nn.py, ops/pallas_kernels.py)"
STEP_LAYER = "step (gluon/fused_step.py)"
# set-up read from the program's own spans: every cell reports them
SETUP = ("setup_import_s", "setup_trace_s", "setup_lower_s", "setup_backend_s",
         "setup_cache_misses", "setup_settle_s", "setup_trace_self_max_s",
         "setup_unaccounted_s")
# kernel-path counters, each with a cell that must report it
KERNEL_PATHS = {"gmm_kernel_path_pct.tok": "mellum2_12b_a2p5b.sft_t8192_ep4share",
                "kda_kernel_path_pct.tok": "kimi_linear_48b_a3b.sft_t16384_ep32share",
                "mhc_kernel_path_pct.tok": "xing4_29b_a4b.sft_t8192_ep8share"}
# readers of the program's span record (chipbench/program_record.py)
PROGRAM = ("step_prepare_ms.img", "step_prepare_ms.tok", "step_launch_ms.img",
           "step_launch_ms.tok", "setup_compile_s", "setup_programs", "setup_step_programs",
           "setup_initialize_s")
# the first per-layer metrics, ahead of every reader of the program's record
FIRST = ("dispatch_ms.img", "collective_exposed_pct.img", "device_idle_pct.img",
         "dispatch_ms.tok", "device_idle_pct.tok")
# the entries appended after those readers, in the order they came
APPENDED = SETUP + ("gmm_kernel_path_pct.tok", "kda_kernel_path_pct.tok", "mhc_step_share_pct.tok",
                    "mhc_roofline_pct.tok", "mhc_kernel_path_pct.tok")


def cells(manifest):
    return [w["name"] for w in manifest["workloads"]]


def listed_once(entries, name):
    """The one entry of a manifest list named `name`."""
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, f"BENCHMARK.json has {len(found)} entries named {name}"
    return found[0]


def entry(manifest, name):
    return listed_once(manifest["per_layer"], name)


def one_line(entry, *keys):
    """Each of the entry's `keys` is 1 to 200 characters on one line, with no tab."""
    for k in keys:
        text = entry[k]
        assert 0 < len(text) <= 200 and "\n" not in text and "\t" not in text, \
            f"{entry['name']}: `{k}` is not one line of at most 200 characters"


def reader(name):
    return run.load_py(os.path.join(run.HERE, "layer_metrics", name + ".py"))


def end_to_end_of(manifest, cell):
    return {m["name"] for m in run.metrics_of(manifest, "end_to_end", cell)}


def in_order(names, wanted):
    """Each of `wanted` is in `names` once, in the same relative order."""
    for n in wanted:
        assert names.count(n) == 1, f"{n} is in the manifest {names.count(n)} times"
    at = [names.index(n) for n in wanted]
    assert at == sorted(at), f"{wanted} lost their relative order"


def per_layer_entries_list_their_cells(manifest):
    """A metric with no `workloads` list applies to every cell, those later
    PRs add too, and a cell whose traced run does not report it is refused."""
    known = set(cells(manifest))
    for m in manifest["per_layer"]:
        listed = m.get("workloads")
        assert isinstance(listed, list) and listed, \
            f"per_layer {m['name']} has no `workloads` list: give it the cells it reads in"
        assert len(set(listed)) == len(listed) and set(listed) <= known, \
            f"per_layer {m['name']}: `workloads` names {sorted(set(listed) - known)} twice or " \
            "not at all among the manifest's cells"


def names_units_and_arrows(manifest):
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = [m["name"] for m in metrics] + cells(manifest) + \
        [c["name"] for c in manifest["configs"]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher") for m in metrics)
    assert sorted(manifest["paths"]) == ["chipbench", "tests/chipbench_tests"]
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4), f"{four} cells on four chips"
    for cell in cells(manifest):   # every cell: setup_s, another end-to-end metric, a per-layer one
        e2e = end_to_end_of(manifest, cell)
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = run.metrics_of(manifest, "per_layer", cell)
        assert layer and all(m["moves"] in e2e for m in layer), \
            f"{cell} lists a per-layer metric that moves none of its end-to-end metrics"


def cell_files(manifest, cell):
    """The runner finds the cell's files by name, under the manifest on disk."""
    on_disk, chips, cell_file, cfg, cfgmod = run.load_cell(cell)
    assert on_disk == manifest
    assert cell_file["name"] == cell and chips in (1, 4)
    assert {"batch", "ring", "loss_band", "why"} <= set(cell_file)
    assert cfg["throughput_metric"] in end_to_end_of(manifest, cell)
    assert all(hasattr(cfgmod, f) for f in ("build", "make_ring", "flops_per_step",
                                            "FLOP_CONVENTION"))
    for m in run.metrics_of(manifest, "per_layer", cell):
        assert callable(reader(m["name"]).read)


def setup_entry(manifest, name):
    """A set-up reader lists EVERY cell: set-up is read everywhere."""
    got = entry(manifest, name)
    assert callable(reader(name).read)
    assert got["layer"] == SETUP_LAYER and got["moves"] == "setup_s"
    assert got["better"] == "lower"
    assert got["unit"] == ("programs" if name == "setup_cache_misses" else "s")
    assert got["source"] == ("program_counter" if name == "setup_cache_misses"
                             else "program_span")
    missing = [c for c in cells(manifest) if c not in got["workloads"]]
    assert not missing, f"append {missing} to the `workloads` of {name}"


def kernel_path_entry(manifest, name):
    got = entry(manifest, name)
    assert callable(reader(name).read)
    assert got["layer"] == OPS_LAYER and got["moves"] == "tokens_per_s"
    assert got["better"] == "higher" and got["source"] == "program_counter"
    assert got["unit"] == "%" and KERNEL_PATHS[name] in got["workloads"], \
        f"append {KERNEL_PATHS[name]} to the `workloads` of {name}"


def program_entry(manifest, name):
    got = entry(manifest, name)
    assert callable(reader(name).read)
    assert got["better"] == "lower"
    if name.startswith("setup_"):
        assert got["moves"] == "setup_s" and got["layer"] == SETUP_LAYER
    else:
        assert got["moves"] == ("images_per_s" if name.endswith(".img") else "tokens_per_s")
        assert got["layer"] == STEP_LAYER
    for cell in got["workloads"]:
        assert got["moves"] in end_to_end_of(manifest, cell), \
            f"{name} lists {cell}, which does not report {got['moves']}"


def order_kept(manifest):
    """What was there keeps its relative order; later entries are appended."""
    names = [m["name"] for m in manifest["per_layer"]]
    in_order(names, FIRST)
    in_order(names, APPENDED)
    assert max(names.index(n) for n in FIRST) < min(names.index(n) for n in PROGRAM)


def every_check(manifest):
    """Every check above, the invariant on `workloads` lists first."""
    per_layer_entries_list_their_cells(manifest)
    names_units_and_arrows(manifest)
    for cell in cells(manifest):
        cell_files(manifest, cell)
    for name in SETUP:
        setup_entry(manifest, name)
    for name in KERNEL_PATHS:
        kernel_path_entry(manifest, name)
    for name in PROGRAM:
        program_entry(manifest, name)
    order_kept(manifest)
