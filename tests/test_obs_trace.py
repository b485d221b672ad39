"""Span tracer (obs.trace): parent nesting, deterministic sampling, ring
capacity, idempotent ends, and the Chrome trace-event exporter schema that
ui.perfetto.dev requires (DESIGN.md §13)."""

import json
import time

from repro.obs.trace import Tracer


def test_span_nesting_and_ids():
    tr = Tracer(sample_rate=1.0)
    root = tr.root("request", top_k=5)
    assert root is not None and root.parent_id is None
    inner = root.child("dispatch", bucket=8)
    assert inner.trace_id == root.trace_id
    assert inner.parent_id == root.span_id
    assert inner.span_id != root.span_id
    inner.end(outcome="ok")
    root.end()
    spans = tr.spans()
    assert [s.name for s in spans] == ["dispatch", "request"]  # finish order
    assert spans[0].t0 >= root.t0 and spans[0].t1 <= spans[1].t1


def test_end_is_idempotent():
    tr = Tracer(sample_rate=1.0)
    sp = tr.root("op")
    sp.end(outcome="failed")
    t1 = sp.t1
    sp.end(outcome="ok")        # second close: ignored entirely
    assert sp.t1 == t1
    assert sp.attrs["outcome"] == "failed"
    assert len(tr.spans()) == 1


def test_deterministic_sampling():
    tr = Tracer(sample_rate=0.25)
    picks = [tr.root("r", i=i) is not None for i in range(12)]
    # every 4th root, starting with the first — no RNG involved
    assert picks == [i % 4 == 0 for i in range(12)]
    assert tr.sampled_roots == 3
    # rate 0 never samples; force bypasses sampling without consuming a slot
    tr0 = Tracer(sample_rate=0.0)
    assert tr0.root("r") is None
    assert tr0.root("swap", force=True) is not None


def test_unsampled_paths_are_none_safe():
    tr = Tracer(sample_rate=0.0)
    parent = tr.root("r")
    assert parent is None
    assert tr.child(parent, "c") is None
    tr.add_span(parent, "phase", 0.0, 1.0)       # silently dropped
    with tr.span(parent, "ctx") as sp:
        assert sp is None
    assert tr.spans() == []


def test_ring_buffer_capacity():
    tr = Tracer(sample_rate=1.0, capacity=8)
    for i in range(20):
        tr.root(f"op{i}").end()
    spans = tr.spans()
    assert len(spans) == 8
    assert spans[0].name == "op12" and spans[-1].name == "op19"


def test_add_span_records_elapsed_interval():
    tr = Tracer(sample_rate=1.0)
    root = tr.root("level")
    t0 = time.perf_counter()
    t1 = t0 + 0.25
    tr.add_span(root, "count_dispatch", t0, t1, chunk=3)
    root.end()
    dispatch = next(s for s in tr.spans() if s.name == "count_dispatch")
    assert dispatch.parent_id == root.span_id
    assert dispatch.duration_s() == 0.25
    assert dispatch.attrs["chunk"] == 3


def test_chrome_export_schema(tmp_path):
    tr = Tracer(sample_rate=1.0)
    root = tr.root("request")
    child = root.child("dispatch")
    child.end()
    root.end(outcome="ok")
    path = tmp_path / "trace.json"
    tr.save_chrome(str(path))

    doc = json.loads(path.read_text())            # must be valid JSON
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    ms = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(xs) == 2 and len(ms) >= 1
    for e in xs:                                   # perfetto-required keys
        assert {"name", "ph", "ts", "dur", "pid", "tid", "args"} <= set(e)
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert {"trace_id", "span_id"} <= set(e["args"])
    assert ms[0]["name"] == "thread_name"
    # the child event nests inside the root event on the µs timeline
    ce = next(e for e in xs if e["name"] == "dispatch")
    re = next(e for e in xs if e["name"] == "request")
    assert ce["args"]["parent_id"] == re["args"]["span_id"]
    assert re["ts"] <= ce["ts"]
    assert ce["ts"] + ce["dur"] <= re["ts"] + re["dur"] + 1e-3
    assert ce["args"]["trace_id"] == re["args"]["trace_id"]


def test_tracer_is_thread_safe_under_concurrent_roots():
    import threading

    tr = Tracer(sample_rate=1.0)

    def burst(n):
        for _ in range(n):
            sp = tr.root("op")
            sp.child("inner").end()
            sp.end()

    threads = [threading.Thread(target=burst, args=(50,)) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tr.spans()
    assert len(spans) == 4 * 50 * 2
    assert len({s.span_id for s in spans}) == len(spans)   # ids never collide
    doc = tr.export_chrome()
    # every event maps to a registered exporter tid (the OS may reuse thread
    # idents across short-lived threads, so only >= 1 distinct is guaranteed)
    tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert tids and all(t >= 1 for t in tids)
