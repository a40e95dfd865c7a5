"""Profiler tests (reference `tests/python/unittest/test_profiler.py`):
chrome-trace dump + aggregate table + Domain/Task/Counter objects."""
import json

import mxnet_tpu as mx
from mxnet_tpu import profiler


def test_profiler_chrome_trace(tmp_path):
    f = str(tmp_path / "trace.json")
    profiler.set_config(filename=f)
    profiler.set_state("run")
    d = profiler.Domain("unit")
    task = d.new_task("work")
    task.start()
    x = mx.np.ones((64, 64))
    (x @ x).wait_to_read()
    task.stop()
    c = d.new_counter("items", 3)
    c.increment(2)
    ev = d.new_event("tick")
    ev.start()
    ev.stop()
    profiler.set_state("stop")
    profiler.dump()

    trace = json.load(open(f))
    events = trace["traceEvents"] if isinstance(trace, dict) else trace
    names = {e.get("name") for e in events}
    assert "work" in names
    assert any(e.get("ph") == "C" for e in events)  # counter samples
    # spans carry duration or begin/end pairs
    assert any(e.get("ph") in ("X", "B") for e in events)


def test_profiler_aggregate_table():
    profiler.set_state("run")
    d = profiler.Domain("agg")
    t = d.new_task("compute")
    t.start()
    t.stop()
    profiler.set_state("stop")
    out = profiler.dumps(format="table")
    assert "compute" in out and "Avg(us)" in out


def test_profiler_records_operators():
    """Ops dispatched while profiling appear as named operator events
    (reference: engine ProfileOperator wrapping)."""
    profiler.dumps(reset=True)
    profiler.set_state("run")
    a = mx.np.ones((8, 8))
    b = (a @ a) + 1
    b.wait_to_read()
    profiler.set_state("stop")
    table = profiler.dumps(format="table")
    assert "matmul" in table or "dot" in table or "add" in table, table
    js = profiler.dumps(format="json", reset=True)
    import json as _json
    events = _json.loads(js)["traceEvents"]
    assert any(e.get("cat") == "operator" for e in events)


def test_profiler_pause_resume():
    profiler.set_state("run")
    profiler.pause()
    assert profiler.state() in ("pause", "paused", "run", "stop")
    profiler.resume()
    profiler.set_state("stop")


def test_dump_memory_profile(tmp_path):
    import os

    import mxnet_tpu.profiler as prof
    p = prof.dump_memory_profile(str(tmp_path / "m.pprof"))
    assert os.path.getsize(p) > 0
