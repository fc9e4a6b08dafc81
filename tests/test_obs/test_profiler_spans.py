"""The one span primitive (``obs/tracer.py::begin`` / ``end``) under ``timer``, ``span`` and
``trace_span``: it writes the span into whichever XProf capture is running, under the
span's own name; and the scope map that names a compiled block's instructions."""

import json

import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu.obs import perf
from sheeprl_tpu.obs import tracer as tr
from sheeprl_tpu.obs.perf import GoodputLedger
from sheeprl_tpu.obs.tracer import span, trace_span
from sheeprl_tpu.utils.timer import timer


@pytest.fixture(autouse=True)
def _clean():
    timer.reset()
    perf.reset()
    yield
    timer.reset()
    perf.reset()


def _start(path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1  # the lowest level that records an annotation
    jax.profiler.start_trace(str(path), profiler_options=options)


def _host_events(path):
    """name -> number of events on the host plane of the capture under ``path``."""
    files = sorted(path.glob("plugins/profile/*/*.xplane.pb"))
    assert files, "the capture wrote no .xplane.pb"
    counts = {}
    for plane in jax.profiler.ProfileData.from_file(str(files[-1])).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    counts[e.name] = counts.get(e.name, 0) + 1
    return counts


@trace_span("Time/decorated")
def _decorated():
    return 7


def test_spans_lie_in_a_capture_under_their_own_names(tmp_path):
    _start(tmp_path)
    try:
        for _ in range(3):
            with timer("Time/phase_outer"):
                with span("Rollout/inner"):
                    jnp.ones(4).block_until_ready()
        assert _decorated() == 7
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    assert events["Time/phase_outer"] == 3 and events["Rollout/inner"] == 3 and events["Time/decorated"] == 1


def test_a_span_that_straddles_the_capture_is_dropped_without_error(tmp_path):
    """The harness starts and stops its capture inside ``Time/phase_env_step``."""
    before = timer("Time/open_at_start")
    before.__enter__()
    _start(tmp_path)
    try:
        before.__exit__(None, None, None)
        with timer("Time/whole"):
            pass
        after = timer("Time/open_at_stop")
        after.__enter__()
    finally:
        jax.profiler.stop_trace()
    after.__exit__(None, None, None)
    events = _host_events(tmp_path)
    assert events.get("Time/whole") == 1
    assert "Time/open_at_start" not in events and "Time/open_at_stop" not in events
    # the timers themselves saw all three
    assert set(timer.to_dict()) == {"Time/open_at_start", "Time/whole", "Time/open_at_stop"}


def test_without_a_capture_or_a_tracer_a_span_only_keeps_time():
    assert tr.get_active() is None
    with timer("Time/quiet"):
        with span("Time/quiet_child"):
            pass
    assert _decorated() == 7
    assert set(timer.to_dict(reset=False)) == {"Time/quiet"}  # span() keeps no time of its own


def test_timer_keeps_the_registry_contract():
    registry = timer._registry  # perfbench's _ResettingTimer holds this very dict
    for _ in range(4):
        with timer("Time/summed"):
            pass
    with timer("Time/once"):
        pass
    assert isinstance(registry["Time/summed"], float) and registry["Time/summed"] >= 0.0
    flushed = timer.to_dict(reset=True)
    assert set(flushed) == {"Time/summed", "Time/once"} and all(isinstance(v, float) for v in flushed.values())
    assert timer._registry is registry and registry == {}


def test_a_disabled_timer_opens_no_span(monkeypatch):
    monkeypatch.setattr(timer, "disabled", True)
    with timer("Time/off"):
        pass
    assert timer.to_dict() == {}


def test_goodput_books_dispatch_once_with_its_children_present():
    timers = {"Time/phase_dispatch": 0.30, "Time/dispatch_sample": 0.10, "Time/dispatch_stage": 0.15, "Time/dispatch_call": 0.05}
    fractions = GoodputLedger().classify(timers, elapsed_s=1.0)
    assert fractions["compute"] == pytest.approx(0.30) and fractions["other"] == pytest.approx(0.70)


# --------------------------------------------------------------------------- scope maps
def _make_scoped():
    """A new function each time: JAX keeps a function's trace, and the scopes are
    declared while it is traced."""

    def _scoped(x, w):
        def loss(w):
            with perf.scope("model/layer"):
                h = jnp.tanh(x @ w)
            with perf.scope("loss"):
                return jnp.sum(h * h)

        value, grad = jax.value_and_grad(loss)(w)
        with perf.scope("optimizer"):
            w = w - 0.1 * grad
        with perf.scope("health"):
            with perf.scope("norms"):
                norm = jnp.sqrt(jnp.sum(grad * grad))
        return w, value, norm

    return _scoped


def test_scope_of_reads_declared_scopes_out_of_op_name_paths():
    for name in ("world_model/rssm", "health", "nan_scan", "actor"):
        with perf.scope(name):
            pass
    scope_of = perf._scope_of
    assert scope_of("jit(block)/jit(main)/while/body/transpose(jvp(world_model/rssm))/while/body/mul") == "world_model/rssm bwd"
    assert scope_of("jit(block)/while/body/closed_call/jvp(world_model/rssm)/WorldModel.dynamic/dot_general") == "world_model/rssm fwd"
    assert scope_of("jit(block)/while/body/health/nan_scan/is_finite") == "health/nan_scan fwd"
    assert scope_of("jit(block)/while/body/imagination/actor/mul") == "actor fwd"  # undeclared names are skipped
    assert scope_of("jit(block)/while/body/concatenate") == "" and scope_of("") == ""


def test_scope_map_of_a_compiled_function_and_its_file(tmp_path):
    perf.PerfPlane({"obs": {"perf": {"enabled": True}}}, log_dir=str(tmp_path))
    x, w = jnp.ones((8, 16)), jnp.ones((16, 16))
    compiled = jax.jit(_make_scoped()).lower(x, w).compile()
    perf.register_compiled("toy/step", compiled)
    doc = json.loads((tmp_path / "scopes" / "toy" / "step.json").read_text())
    assert doc["program"] == "toy/step" and doc["module"] == "jit__scoped"
    found = {key for shares in doc["ops"].values() for key in shares}
    assert {"model/layer fwd", "model/layer bwd", "optimizer fwd", "health/norms fwd"} <= found
    assert all(sum(shares.values()) == pytest.approx(1.0) for shares in doc["ops"].values() if shares)
    assert (doc["ops"], doc["inherited"]) == perf.scope_map(compiled.as_text())
    # every instruction the map names is one of the program's own
    assert all(name in compiled.as_text() for name in doc["ops"])


def test_no_scope_file_without_a_log_dir_and_scopes_change_no_flops(tmp_path):
    x, w = jnp.ones((8, 16)), jnp.ones((16, 16))
    compiled = jax.jit(_make_scoped()).lower(x, w).compile()
    perf.register_compiled("toy/step", compiled)  # no PerfPlane with a log dir: nothing is written
    assert not list(tmp_path.iterdir()) and "toy/step" in perf.registered_cost_models()

    def plain(x, w):
        value, grad = jax.value_and_grad(lambda w: jnp.sum(jnp.tanh(x @ w) ** 2))(w)
        return w - 0.1 * grad, value, jnp.sqrt(jnp.sum(grad * grad))

    assert perf.analyze_compiled(compiled)[0] == perf.analyze_compiled(jax.jit(plain).lower(x, w).compile())[0]


def test_the_scopes_tag_puts_the_declared_names_into_the_program():
    """JAX's persistent cache keys a program without its metadata: the tag is what keeps
    it from handing a scoped program the executable of a source with other scopes."""
    x, w = jnp.ones((8, 16)), jnp.ones((16, 16))

    def tagged(scoped):
        def fn(x, w):
            w, value, norm = scoped(x, w)
            with perf.scopes_tag():
                return w, value + 1.0, norm

        return jax.jit(fn)

    def plain(x, w):
        value, grad = jax.value_and_grad(lambda w: jnp.sum(jnp.tanh(x @ w) ** 2))(w)
        with perf.scopes_tag():  # no scope declared: nothing is tagged
            return w - 0.1 * grad, value + 1.0, jnp.sqrt(jnp.sum(grad * grad))

    assert "frontend_attributes" not in jax.jit(plain).lower(x, w).as_text()
    lowered = tagged(_make_scoped()).lower(x, w)
    assert 'scopes = "health,loss,model/layer,norms,optimizer"' in lowered.as_text()
    # the attribute is not debug info: what the default key hashes (the module less its locations) holds it
    from jax._src import cache_key

    assert b"health,loss,model/layer,norms,optimizer" in cache_key._canonicalize_ir(lowered.compiler_ir(), cache_key.IgnoreCallbacks.NO)
    assert b"model/layer" not in cache_key._canonicalize_ir(jax.jit(_make_scoped()).lower(x, w).compiler_ir(), cache_key.IgnoreCallbacks.NO)
    # and the compiled program does the same work
    assert perf.analyze_compiled(lowered.compile())[0] == perf.analyze_compiled(jax.jit(plain).lower(x, w).compile())[0]


def test_instrument_writes_the_map_of_a_scoped_program_only(tmp_path):
    cfg = {"obs": {"perf": {"enabled": True}}}
    perf.PerfPlane(cfg, log_dir=str(tmp_path))
    x, w = jnp.ones((8, 16)), jnp.ones((16, 16))
    scoped = perf.instrument(cfg, "toy/step", jax.jit(_make_scoped()))
    scoped(x, w), scoped(x, w)
    plain = perf.instrument(cfg, "toy/plain", jax.jit(lambda x, w: jnp.tanh(x @ w)))  # its text holds none of the declared scopes
    plain(x, w), plain(x, w)
    assert (tmp_path / "scopes" / "toy" / "step.json").is_file() and not (tmp_path / "scopes" / "toy" / "plain.json").exists()
    assert perf.registered_cost_models()["toy/step"]["calls"] == 2


def test_a_note_made_while_tracing_is_a_top_level_key_of_the_map_and_one_log_line(tmp_path, caplog):
    """``perf.note`` from inside the traced function: what the program's trace said of
    itself (which mechanism it engaged, on how much) goes into the scope map beside
    the keys a reader knows, is logged at the registration, and is gone after a reset."""
    cfg = {"obs": {"perf": {"enabled": True}}}
    perf.PerfPlane(cfg, log_dir=str(tmp_path))
    scoped = _make_scoped()

    def noting(x, w):
        perf.note("deferred_wgrad", {"kernels": 1, "parameters": int(w.size)})
        return scoped(x, w)

    x, w = jnp.ones((8, 16)), jnp.ones((16, 16))
    fn = perf.instrument(cfg, "toy/step", jax.jit(noting))
    with caplog.at_level("INFO", logger="sheeprl_tpu.obs.perf"):
        fn(x, w), fn(x, w)
    doc = json.loads((tmp_path / "scopes" / "toy" / "step.json").read_text())
    assert doc["deferred_wgrad"] == {"kernels": 1, "parameters": 256} and {"program", "module", "ops", "inherited"} < set(doc)
    lines = [r.getMessage() for r in caplog.records if "deferred_wgrad" in r.getMessage()]
    assert len(lines) == 1 and "toy/step" in lines[0] and '"parameters": 256' in lines[0]
    perf.reset()
    perf.PerfPlane(cfg, log_dir=str(tmp_path / "again"))
    perf.register_compiled("toy/step", jax.jit(_make_scoped()).lower(x, w).compile())
    assert "deferred_wgrad" not in json.loads((tmp_path / "again" / "scopes" / "toy" / "step.json").read_text())


def test_hlo_text_forms_the_parser_meets():
    for name in ("rssm", "opt"):
        with perf.scope(name):
            pass
    text = """HloModule jit_block, entry_computation_layout={()->f32[]}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.3 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(block)/transpose(jvp(rssm))/mul"}
}

%fused_computation.2 (param_0: f32[8]) -> (f32[], f32[8]) {
  %param_0.1 = f32[8]{0} parameter(0)
  %multiply.4 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(block)/opt/mul"}
  %add.6 = f32[8]{0} add(%multiply.4, %param_0.1), metadata={op_name="jit(block)/opt/add"}
  %reduce.1 = f32[] reduce(%add.6, %c), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(block)/rssm/reduce_sum"}
  ROOT %tuple.1 = (f32[], f32[8]{0}) tuple(%reduce.1, %add.6)
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %fusion.7 = f32[8]{0} fusion(%gte), kind=kLoop, calls=%fused_computation.1
  %multiply_reduce_fusion.8 = (f32[], f32[8]{0}) fusion(%fusion.7), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(block)/rssm/reduce_sum"}
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%i, %fusion.7)
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="carry"}
  %while.232 = (s32[], f32[8]{0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(block)/transpose(jvp(rssm))/while"}
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%Arg_0.1)
  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
  %convert.6 = bf16[8]{0} convert(%Arg_0.1), metadata={op_name="jit(block)/while/body/closed_call/Dense_0/convert_element_type"}
  %tuple.8 = (bf16[8]{0}) tuple(%convert.6)
  %while.232 = (s32[], f32[8]{0}) while(%tuple.8), condition=%cond, body=%body, metadata={op_name="jit(block)/transpose(jvp(rssm))/while"}
  %custom-call.3 = f32[8]{0} custom-call(), custom_call_target="AllocateBuffer"
  ROOT %add.5 = f32[8]{0} add(%copy-done.1, %copy-done.1), metadata={op_name="jit(block)/opt/add"}
}
"""
    ops, inherited = perf.scope_map(text)
    assert ops["fusion.7"] == {"rssm bwd": 1.0}  # a fusion without a name of its own: the scopes of what it holds
    # split by what it holds, two instructions of the optimizer and one of the reduction it is rooted at
    assert ops["multiply_reduce_fusion.8"] == {"opt fwd": pytest.approx(2 / 3), "rssm fwd": pytest.approx(1 / 3)}
    assert ops["while.232"] == {"rssm bwd": 1.0} and ops["add.5"] == {"opt fwd": 1.0}
    # the compiler's copies carry no metadata: they are charged to the scope that reads them
    assert ops["copy-done.1"] == ops["copy-start.1"] == {"opt fwd": 1.0}
    # work hoisted out of a scan without the outer name stack goes with the loop it feeds
    assert ops["convert.6"] == ops["tuple.8"] == {"rssm bwd": 1.0}
    assert {"convert.6", "copy-done.1", "copy-start.1", "tuple.8"} <= set(inherited) and not {"add.5", "fusion.7", "custom-call.3"} & set(inherited)
    assert ops["custom-call.3"] == {}  # nothing reads it, it reads nothing: unscoped
    assert "multiply.3" not in ops and "param_0" not in ops  # inside the fusion: never a trace event
