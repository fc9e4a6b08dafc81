"""Span tracer: nesting, Chrome-trace export format, percentiles, thread tracks."""

import json
import threading
import time

import pytest

from sheeprl_tpu.obs import tracer as tr
from sheeprl_tpu.obs.tracer import SpanTracer, span, trace_span
from sheeprl_tpu.utils.timer import timer


@pytest.fixture()
def tracer():
    t = SpanTracer(rank=0)
    prev = tr.set_active(t)
    yield t
    tr.set_active(prev)


def _x_events(tracer):
    return [e for e in tracer.chrome_trace()["traceEvents"] if e["ph"] == "X"]


def test_span_nesting_depth_and_order(tracer):
    with span("outer"):
        with span("inner"):
            time.sleep(0.001)
    events = {e["name"]: e for e in _x_events(tracer)}
    assert set(events) == {"outer", "inner"}
    assert events["inner"]["args"]["depth"] == 1
    assert events["outer"]["args"]["depth"] == 0
    # the child slice lies inside the parent slice
    assert events["outer"]["ts"] <= events["inner"]["ts"]
    assert events["inner"]["ts"] + events["inner"]["dur"] <= events["outer"]["ts"] + events["outer"]["dur"] + 1e-3


def test_chrome_trace_is_valid_json_with_metadata(tracer, tmp_path):
    with span("Time/phase"):
        pass
    path = tracer.export_chrome_trace(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    assert doc["displayTimeUnit"] == "ms"
    phs = {e["ph"] for e in doc["traceEvents"]}
    assert phs == {"M", "X"}  # metadata + complete events, the Perfetto-loadable subset
    x = [e for e in doc["traceEvents"] if e["ph"] == "X"][0]
    assert x["pid"] == 0 and x["dur"] >= 0 and "ts" in x and x["cat"] == "sheeprl_tpu"


def test_timer_blocks_become_spans(tracer):
    with timer("Time/env_interaction_time"):
        with timer("Time/phase_player"):
            pass
    names = {e["name"] for e in _x_events(tracer)}
    assert names == {"Time/env_interaction_time", "Time/phase_player"}
    # and the flat timer registry still accumulates independently
    assert "Time/env_interaction_time" in timer.to_dict(reset=True)


def test_decorator_and_percentiles(tracer):
    @trace_span("Time/fn")
    def fn(x):
        return x + 1

    for i in range(10):
        assert fn(i) == i + 1
    stats = tracer.percentiles(reset=True)["Time/fn"]
    assert stats["count"] == 10
    assert 0 <= stats["p50"] <= stats["p95"] <= stats["p99"]
    # reset=True drained the histogram
    assert tracer.percentiles() == {}


def test_threads_get_separate_tracks(tracer):
    # both workers are alive at once, so the second cannot be handed the first's thread id
    both_alive = threading.Barrier(2)

    def work():
        with span("Time/worker"):
            both_alive.wait(timeout=10)

    threads = [threading.Thread(target=work) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    with span("Time/main"):
        pass
    tids = {e["tid"] for e in _x_events(tracer)}
    assert len(tids) == 3  # two workers + main


def test_no_active_tracer_is_noop():
    assert tr.get_active() is None
    with span("ignored"):
        pass
    with timer("Time/ignored"):
        pass

    @trace_span("ignored")
    def fn():
        return 42

    assert fn() == 42
    timer.reset()


def test_max_events_bounded():
    t = SpanTracer(rank=0, max_events=5)
    prev = tr.set_active(t)
    try:
        for _ in range(10):
            with span("s"):
                pass
    finally:
        tr.set_active(prev)
    assert len(t) == 5
    assert t.dropped_events == 5
    # histograms keep feeding past the event cap
    assert t.percentiles()["s"]["count"] == 10
