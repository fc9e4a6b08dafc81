"""The reduction of a capture by the program's own names (``perfbench/readers/spans.py``).

The hand-written capture (an XSpace encoded below, times in ms) holds, on the host's
``python3`` thread, two iterations of the program's spans:

    Time/env_interaction_time 0-2 > Time/phase_player 0.1-1.9 > Rollout/action_fetch 0.2-1.5
    Time/phase_dispatch 2-5 > Time/dispatch_sample 2-2.5, Time/dispatch_stage 2.5-4.5,
                              Time/dispatch_call 4.5-4.7 and 4.7-4.9 (two chunks)
    Time/env_interaction_time 5-12 > Time/phase_player 5.1-11.9 > Rollout/action_fetch 5.2-11.5

and on the device: ``jit_player_step`` 1.0-1.4 (one op, ``%fusion.1``, the name of an op of
the block too); ``jit_block`` 5-11, whole, with ``%fusion.1`` 5-6, ``%while.5`` 6-10 holding
``%fusion.2`` 6.5-7.5 and the nested ``%while.6`` 7.5-9.5, which holds ``%fusion.3`` 8-9, then
``%copy.9`` 10-11; and a second ``jit_block`` 11.5-13 that outlasts the last whole span.
The scope map names fusion.1 (encoder), while.5 (rssm, backward), fusion.2 (three quarters
rssm backward, a quarter health: a fusion that holds instructions of both), while.6
(imagination, inherited from a neighbour), gives copy.9 no scope and does not hold fusion.3.

``chip_spans.xplane.pb.gz`` + ``chip_spans.scopes.json.gz`` are a capture of a TPU v5e and the
scope map the program wrote in that run (PERF.md section 6, PR 25, says how they were made).
"""

import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench.readers import spans, xplane

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns

HOST = [
    ("Time/env_interaction_time", 0.0, 2.0),
    ("Time/phase_player", 0.1, 1.9),
    ("Rollout/action_fetch", 0.2, 1.5),
    ("Time/phase_dispatch", 2.0, 5.0),
    ("Time/dispatch_sample", 2.0, 2.5),
    ("Time/dispatch_stage", 2.5, 4.5),
    ("Time/dispatch_call", 4.5, 4.7),
    ("Time/dispatch_call", 4.7, 4.9),
    ("PjitFunction(block)", 4.5, 4.9),
    ("Time/env_interaction_time", 5.0, 12.0),
    ("Time/phase_player", 5.1, 11.9),
    ("Rollout/action_fetch", 5.2, 11.5),
]
MODULES = [("jit_player_step(2)", 1.0, 1.4), ("jit_block(1)", 5.0, 11.0), ("jit_block(1)", 11.5, 13.0)]
OPS = [
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fc", 1.0, 1.4),
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fc", 5.0, 6.0),
    ("%while.5 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), condition=%c, body=%b", 6.0, 10.0),
    ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop, calls=%fd", 6.5, 7.5),
    ("%while.6 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %u), condition=%c2, body=%b2", 7.5, 9.5),
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %r), kind=kLoop, calls=%fe", 8.0, 9.0),
    ("%copy.9 = f32[8]{0} copy(f32[8]{0} %s)", 10.0, 11.0),
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fc", 11.5, 12.5),
]
SCOPES = {
    "program": "dreamer_v3/train_block",
    "module": "jit_block",
    "ops": {
        "fusion.1": {"world_model/encoder fwd": 1.0},
        "while.5": {"world_model/rssm bwd": 1.0},
        "fusion.2": {"world_model/rssm bwd": 0.75, "health fwd": 0.25},
        "while.6": {"imagination fwd": 1.0},
        "copy.9": {},
    },
    "inherited": ["while.6"],
}


# --------------------------------------------------------------------------- a tiny XSpace writer
def _varint(n):
    out = bytearray()
    while True:
        n, low = n >> 7, n & 0x7F
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field, value):
    return _varint(field << 3) + _varint(value)


def _bytes(field, payload):
    payload = payload.encode() if isinstance(payload, str) else payload
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _plane(plane_id, name, lines):
    """``lines``: {line name: [(event name, start ms, end ms)]}; XPlane{id=1,name=2,lines=3,event_metadata=4}."""
    names = sorted({e[0] for events in lines.values() for e in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    body = _int(1, plane_id) + _bytes(2, name)
    for k, (line_name, events) in enumerate(lines.items()):
        line = _int(1, k + 1) + _bytes(2, line_name)  # XLine{id=1,name=2,timestamp_ns=3 (0),events=4}
        for event_name, a, b in events:  # XEvent{metadata_id=1,offset_ps=2,duration_ps=3}
            line += _bytes(4, _int(1, ids[event_name]) + _int(2, round(a * MS) * 1000) + _int(3, round((b - a) * MS) * 1000))
        body += _bytes(3, line)
    for n, i in ids.items():  # map<int64, XEventMetadata{id=1,name=2}>
        body += _bytes(4, _int(1, i) + _bytes(2, _int(1, i) + _bytes(2, n)))
    return _bytes(1, body)  # XSpace{planes=1}


def write_capture(out: Path, host=HOST, modules=MODULES, ops=OPS) -> Path:
    prof = out / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    space = _plane(1, "/device:TPU:0", {"XLA Modules": modules, "XLA Ops": ops}) + _plane(2, "/host:CPU", {"python3": host})
    (prof / "host.xplane.pb").write_bytes(space)
    return prof / "host.xplane.pb"


@pytest.fixture()
def reduction(tmp_path):
    pd = xplane.load(write_capture(tmp_path))
    return spans.reduce_capture(pd, {"jit_block": SCOPES})


# --------------------------------------------------------------------------- the hand-written capture
def test_host_spans_by_name_with_depth(reduction):
    s = reduction["spans"]
    assert "PjitFunction(block)" not in s and reduction["window_s"] == pytest.approx(0.012)
    assert s["Time/phase_player"] == {"seconds": pytest.approx(0.0086), "calls": 2, "depth": 1}
    assert s["Rollout/action_fetch"] == {"seconds": pytest.approx(0.0076), "calls": 2, "depth": 2}
    assert s["Time/dispatch_call"] == {"seconds": pytest.approx(0.0004), "calls": 2, "depth": 1}
    assert s["Time/phase_dispatch"]["depth"] == 0


def test_every_nanosecond_of_a_whole_execution_is_attributed_once(reduction):
    assert set(reduction["device"]) == {"jit_block"}  # the player has no map; its fusion.1 is not the block's
    block = reduction["device"]["jit_block"]
    assert block["executions"] == 1 and block["op_events"] == 6  # the second execution outlasts the window
    assert block["module_s"] == pytest.approx(0.006) and block["busy_s"] == pytest.approx(0.006)
    assert block["scopes"] == {
        "world_model/encoder fwd": pytest.approx(0.001),
        "world_model/rssm bwd": pytest.approx(0.00175),  # the loop's own 1 ms + its share of fusion.2; the nested loop is not counted twice
        "health fwd": pytest.approx(0.00025),  # the other share of fusion.2, which holds instructions of both scopes
        "imagination fwd": pytest.approx(0.001),  # the nested loop less its body
        "unscoped": pytest.approx(0.002),  # fusion.3 is not in the map, copy.9 has no scope
    }
    assert sum(block["scopes"].values()) == pytest.approx(block["busy_s"]) and block["inherited_s"] == pytest.approx(0.001)


def test_idle_gaps_are_labelled_by_the_deepest_span_that_covers_most(reduction):
    assert reduction["busy_s"] == pytest.approx(0.0069) and reduction["idle_s"] == pytest.approx(0.0051)
    assert reduction["idle_by_span"] == {"Time/dispatch_stage": pytest.approx(0.0036), "Rollout/action_fetch": pytest.approx(0.0015)}
    longest = reduction["longest_gaps"][0]
    assert longest["span"] == "Time/dispatch_stage" and longest["seconds"] == pytest.approx(0.0036)
    assert longest["under"]["Time/phase_dispatch"] == pytest.approx(0.003)
    assert longest["under"]["Time/dispatch_sample"] == pytest.approx(0.0005)
    assert spans.label_gap((0.020, 0.021), [("Time/x", 0.0, 0.0203, 0)]) == ("no span", {"Time/x": pytest.approx(0.0003)})


def test_a_capture_that_lost_device_events_is_called_out(tmp_path):
    ops = OPS[:3] + OPS[4:] + [(n, a + 7.0, b + 7.0) for n, a, b in OPS[1:7]]  # a second whole block, the first less one op
    host = HOST + [("Time/phase_env_step", 12.0, 19.0)]
    red = spans.reduce_capture(xplane.load(write_capture(tmp_path, host=host, modules=MODULES[:2] + [("jit_block(1)", 12.0, 18.0)], ops=ops)), {"jit_block": SCOPES})
    assert red["device"]["jit_block"]["executions"] == 2 and red["device"]["jit_block"]["fewest_op_events"] == 5
    assert any("lost device events" in line for line in spans.tables(red))
    assert not any("lost device events" in line for line in spans.tables(spans.reduce_capture(xplane.load(write_capture(tmp_path / "sound")), {"jit_block": SCOPES})))


def test_self_seconds_of_overlapping_events():
    events = [("a", 1.0, 3.0), ("b", 2.5, 4.0), ("loop", 5.0, 8.0), ("body", 5.5, 6.0), ("late", 9.0, 9.5)]
    assert spans.self_seconds(events) == pytest.approx([1.5, 1.5, 2.5, 0.5, 0.5])
    assert spans.self_seconds([]) == []


@pytest.fixture()
def traced_run(tmp_path, monkeypatch):
    from perfbench import harness

    monkeypatch.setattr(harness, "OUT", tmp_path)
    spans._CACHE.clear()
    write_capture(tmp_path / "trace" / "a_cell")
    scopes = tmp_path / "logs" / "a_cell" / "runs" / "dreamer_v3" / "env" / "run" / "version_0" / "scopes" / "dreamer_v3"
    scopes.mkdir(parents=True)
    (scopes / "train_block.json").write_text(json.dumps(SCOPES))
    (scopes.parent.parent / "perf_report.json").write_text("{}")  # other JSON of the run is not a map
    window = {"grad_steps": 8, "blocks": 4, "iterations": 8, "spans": {k: {"seconds": 0.1, "calls": 4} for k in ("dispatch", "buffer_add")}}
    adapter = SimpleNamespace(intervals=[("dispatch", 10.002, 10.005), ("buffer_add", 10.0, 10.001), ("dispatch", 10.1, 10.104)])
    return {"traced": True, "cell": SimpleNamespace(name="a_cell"), "window": window, "adapter": adapter}


def test_metric_readers_on_a_traced_run(traced_run, capsys):
    assert spans.action_wait_ms(traced_run) == pytest.approx(3.8)  # 7.6 ms of action_fetch over 2 iterations
    assert spans.dispatch_sample_ms(traced_run) == pytest.approx(0.5)
    assert spans.dispatch_stage_ms(traced_run) == pytest.approx(2.0)
    assert spans.dispatch_call_ms(traced_run) == pytest.approx(0.4)  # both chunks of the one block
    # the window held two gradient steps a block, so the one whole execution is two steps
    assert spans.rssm_device_ms(traced_run) == pytest.approx(0.875)
    assert spans.imagination_device_ms(traced_run) == pytest.approx(0.5)
    assert spans.optimizer_device_ms(traced_run) == 0.0 and spans.health_device_ms(traced_run) == pytest.approx(0.125)
    assert spans.unscoped_device_share(traced_run) == pytest.approx(100.0 / 3.0)
    printed = capsys.readouterr().err
    assert printed.count("perfbench: spans window") == 1  # parsed and printed once in a process
    assert "world_model/rssm bwd" in printed and "Time/phase_dispatch 3.000 ms a call over 1 calls" in printed
    assert "pair around the same place 3.500 ms over 2 calls" in printed


def test_readers_find_nothing_without_a_capture_spans_or_a_map(traced_run, tmp_path):
    untraced = {**traced_run, "traced": False}
    assert spans.action_wait_ms(untraced) is None and spans.rssm_device_ms(untraced) is None
    other = {**traced_run, "cell": SimpleNamespace(name="no_such_cell")}
    assert spans.dispatch_call_ms(other) is None and spans.unscoped_device_share(other) is None
    # a program without the spans and the map (the parent commit): every reader returns None
    spans._CACHE.clear()
    bare = {**traced_run, "cell": SimpleNamespace(name="parent_cell")}
    write_capture(tmp_path / "trace" / "parent_cell", host=[("PjitFunction(block)", 4.5, 4.9)])
    for reader in (spans.action_wait_ms, spans.dispatch_sample_ms, spans.dispatch_stage_ms, spans.dispatch_call_ms):
        assert reader(bare) is None
    for reader in (spans.rssm_device_ms, spans.imagination_device_ms, spans.optimizer_device_ms, spans.health_device_ms, spans.unscoped_device_share):
        assert reader(bare) is None


def test_the_tool_prints_the_same_tables(tmp_path, capsys):
    from perfbench.tools import spans as tool

    write_capture(tmp_path / "xprof")
    (tmp_path / "scopes").mkdir()
    (tmp_path / "scopes" / "train_block.json").write_text(json.dumps(SCOPES))
    assert tool.main([str(tmp_path / "xprof")]) == 0  # <log_dir>/scopes beside <log_dir>/xprof
    out = capsys.readouterr().out
    assert "scope maps for modules ['jit_block']" in out and "world_model/rssm bwd" in out and "Time/dispatch_stage" in out
    assert tool.main([]) == 2


def test_the_tool_looks_for_the_maps_where_a_run_leaves_them_and_nowhere_else(tmp_path, capsys):
    from perfbench.tools import spans as tool

    # a perfbench run: .perfbench/trace/<cell> and .perfbench/logs/<cell>/**/scopes
    capture = write_capture(tmp_path / "out" / "trace" / "a_cell")
    scopes = tmp_path / "out" / "logs" / "a_cell" / "runs" / "version_0" / "scopes" / "dreamer_v3"
    scopes.mkdir(parents=True)
    (scopes / "train_block.json").write_text(json.dumps(SCOPES))
    assert tool.scopes_of(capture) == tmp_path / "out" / "logs" / "a_cell"
    assert tool.main([str(capture)]) == 0 and "health fwd" in capsys.readouterr().out
    # a scopes/ directory further up is not the run's: the walk does not leave the run
    (tmp_path / "scopes").mkdir()
    (tmp_path / "scopes" / "train_block.json").write_text(json.dumps(SCOPES))
    lone = write_capture(tmp_path / "elsewhere" / "deep" / "xprof")
    assert tool.scopes_of(lone) is None
    assert tool.main([str(lone)]) == 0
    out = capsys.readouterr().out
    assert "not grouped" in out and "world_model/rssm bwd" not in out and "Time/dispatch_stage" in out
    assert tool.main([str(lone), str(tmp_path / "scopes")]) == 0 and "world_model/rssm bwd" in capsys.readouterr().out


# --------------------------------------------------------------------------- the recorded chip capture
@pytest.fixture(scope="module")
def chip():
    capture, scopes = DATA / "chip_spans.xplane.pb.gz", DATA / "chip_spans.scopes.json.gz"
    if not capture.is_file():
        pytest.skip("no chip capture with spans kept")
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(gzip.decompress(capture.read_bytes()))
    doc = json.loads(gzip.decompress(scopes.read_bytes()))
    return spans.reduce_capture(pd, {doc["module"]: doc}), doc


def test_chip_capture_holds_the_programs_spans(chip):
    red, _ = chip
    for name in ("Time/phase_player", "Time/phase_dispatch", "Time/dispatch_sample", "Time/dispatch_stage", "Time/dispatch_call", "Rollout/action_fetch", "Time/phase_buffer_add"):
        assert red["spans"][name]["calls"] >= 1, name
    assert red["spans"]["Time/dispatch_call"]["depth"] > red["spans"]["Time/phase_dispatch"]["depth"]
    children = sum(red["spans"][f"Time/dispatch_{k}"]["seconds"] for k in ("sample", "stage", "call"))
    assert 0.5 * red["spans"]["Time/phase_dispatch"]["seconds"] < children <= red["spans"]["Time/phase_dispatch"]["seconds"]


def test_chip_capture_device_time_by_scope(chip):
    red, doc = chip
    block = red["device"][doc["module"]]
    assert block["executions"] >= 1 and block["op_events"] > 100
    # every nanosecond once: the scopes add up to the busy time, which a module's execution bounds
    assert sum(block["scopes"].values()) == pytest.approx(block["busy_s"]) and block["busy_s"] <= block["module_s"] * 1.0001
    scoped = {k.split(" ")[0] for k in block["scopes"]}
    assert {"world_model/rssm", "imagination", "wm_optimizer", "health"} <= scoped
    assert any(k.endswith(" bwd") for k in block["scopes"])
    assert block["scopes"].get("unscoped", 0.0) < 0.25 * block["busy_s"]
    assert red["longest_gaps"] and all(g["span"].startswith(spans.SPAN_PREFIXES) or g["span"] == "no span" for g in red["longest_gaps"])
