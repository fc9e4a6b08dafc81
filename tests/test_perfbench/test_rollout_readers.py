"""The readers of recurrent PPO's host loop (``perfbench/readers/rollout.py``) on a capture
written by hand with ``test_spans.py``'s XSpace writer (times in ms).

Host, on one thread: two rollouts of two acting iterations each, the second the first moved
by 22 ms, and the update boundary between them:

    Time/env_interaction_time 0-9.5 >
        Rollout/act_call 0-0.6, Rollout/action_fetch 0.7-3.0, Rollout/env_step 3.1-3.6,
        Rollout/truncation_value 3.6-4.2, Rollout/store 4.2-4.5,
        Rollout/act_call 4.6-5.2, Rollout/action_fetch 5.3-8.0, Rollout/env_step 8.1-8.6, Rollout/store 8.6-9.0
    Time/update_prep 9.6-11.0
    Time/train_time 11.0-19.9 > Time/update_call 11.0-11.5, Time/update_fetch 11.5-19.8
    Time/update_after 20.0-20.8, Time/rollout_prep 21.2-22.0
    Time/env_interaction_time 22.0-31.5 > (as the first)

Device, one op a module: ``jit_act`` 0.8-2.5 (launched 0.2 after its call returned, back
0.5 before its fetch ended) and 5.1-7.0 (begun 0.1 before its call returned, back 1.0), the
truncation's ``jit_value`` 3.7-4.0, then the bootstrap's ``jit_value`` 9.8-10.0, ``jit_gae``
10.2-10.3, ``jit_train_fn`` 11.3-19.5 and the carry's copy 21.5-21.8; the second rollout's as
the first's, 22 ms later.  The one device gap that no span covers more than half of is
19.5-21.5 (``Time/update_after`` covers 0.8 of its 2.0 ms).
"""

from types import SimpleNamespace

import pytest
from test_spans import write_capture

from perfbench.readers import rollout, spans, xplane

ITERATIONS = [
    ("Rollout/act_call", 0.0, 0.6),
    ("Rollout/action_fetch", 0.7, 3.0),
    ("Rollout/env_step", 3.1, 3.6),
    ("Rollout/truncation_value", 3.6, 4.2),
    ("Rollout/store", 4.2, 4.5),
    ("Rollout/act_call", 4.6, 5.2),
    ("Rollout/action_fetch", 5.3, 8.0),
    ("Rollout/env_step", 8.1, 8.6),
    ("Rollout/store", 8.6, 9.0),
]
ACTING = [("jit_act(3)", 0.8, 2.5), ("jit_value(4)", 3.7, 4.0), ("jit_act(3)", 5.1, 7.0)]
BOUNDARY = [
    ("Time/update_prep", 9.6, 11.0),
    ("Time/train_time", 11.0, 19.9),
    ("Time/update_call", 11.0, 11.5),
    ("Time/update_fetch", 11.5, 19.8),
    ("Time/update_after", 20.0, 20.8),
    ("Time/rollout_prep", 21.2, 22.0),
]
UPDATE = [("jit_value(4)", 9.8, 10.0), ("jit_gae(5)", 10.2, 10.3), ("jit_train_fn(6)", 11.3, 19.5), ("jit_copy(7)", 21.5, 21.8)]
SHIFT = 22.0


def _moved(events, by):
    return [(name, a + by, b + by) for name, a, b in events]


HOST = [("Time/env_interaction_time", 0.0, 9.5), *ITERATIONS, *BOUNDARY, ("Time/env_interaction_time", SHIFT, SHIFT + 9.5), *_moved(ITERATIONS, SHIFT)]
MODULES = ACTING + UPDATE + _moved(ACTING, SHIFT)
OPS = [(f"%fusion.{i} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop, calls=%f{i}", a, b) for i, (_, a, b) in enumerate(MODULES)]
BUSY_MS = sum(b - a for _, a, b in MODULES)  # 16.6: no two overlap


@pytest.fixture()
def run_of(tmp_path, monkeypatch):
    """A traced run whose capture holds ``host`` on the host's thread and the modules above."""
    from perfbench import harness

    monkeypatch.setattr(harness, "OUT", tmp_path)

    def of(cell, host=HOST):
        spans._CACHE.clear()
        rollout._CACHE.clear()
        write_capture(tmp_path / "trace" / cell, host=host, modules=MODULES, ops=OPS)
        return {"traced": True, "cell": SimpleNamespace(name=cell), "window": {"grad_steps": 4, "blocks": 2}}

    return of


def test_the_acting_round_trip_is_split_on_the_devices_clock(run_of):
    run = run_of("a_cell")
    assert rollout.act_launch_ms(run) == pytest.approx((0.2 - 0.1) / 2, abs=1e-6)  # signed: the second began before its call returned
    assert rollout.act_return_ms(run) == pytest.approx((0.5 + 1.0) / 2, abs=1e-6)
    # env_step 0.5 + 0.5, the truncation's bootstrap 0.6, store 0.3 + 0.4, a rollout of two calls
    assert rollout.rollout_host_ms(run) == pytest.approx(2.3 / 2, abs=1e-6)


def test_the_update_boundarys_idle_is_the_gap_between_rollouts_less_the_device_busy_in_it(run_of, capsys):
    run = run_of("a_cell")
    # 9.5-22.0, of which the bootstrap 0.2, the advantages 0.1, the update 8.2 and the copy 0.3 are busy
    assert rollout.update_boundary_idle_ms(run) == pytest.approx(12.5 - 8.8, abs=1e-6)
    printed = capsys.readouterr().err
    for part in ("Time/update_prep 1.1000", "Time/update_call 0.3000", "Time/update_fetch 0.3000", "Time/update_after 0.8000", "Time/rollout_prep 0.5000", "under none of them 0.7000"):
        assert part in printed, printed


def test_the_iteration_closes_in_the_log(run_of, capsys):
    rollout.rollout_host_ms(run_of("a_cell"))
    printed = capsys.readouterr().err
    # 4.5 ms an iteration: act_call 0.6 + launch 0.05 + jit_act 1.8 + return 0.75 + host 1.15 = 4.35, and 0.4 ms between the spans
    assert "the iteration closes: act_call 0.6000 + launch 0.0500 + jit_act 1.8000 + return 0.7500 + host 1.1500 = 4.3500 ms against 4.7500" in printed
    assert "bootstrap calls a rollout 1.000 (at most 1)" in printed


def test_only_the_idle_that_no_span_covers_counts_as_unspanned(run_of):
    run = run_of("a_cell")
    red = spans.of_run(run)
    assert red["busy_s"] == pytest.approx(1e-3 * BUSY_MS) and red["idle_by_span"]["no span"] == pytest.approx(2.0e-3)
    assert rollout.idle_unspanned_share(run) == pytest.approx(100.0 * 2.0 / (31.5 - BUSY_MS), abs=1e-6)
    # the same capture with the gap under Time/update_after for more than half of it: nothing is unspanned
    covered = [(n, a, 21.2 if n == "Time/update_after" else b) for n, a, b in HOST]
    assert rollout.idle_unspanned_share(run_of("covered", covered)) == 0.0


def test_the_readers_find_nothing_without_the_spans(run_of):
    readers = (rollout.act_launch_ms, rollout.act_return_ms, rollout.rollout_host_ms, rollout.update_boundary_idle_ms, rollout.idle_unspanned_share)
    assert all(reader({"traced": False}) is None for reader in readers)
    bare = run_of("bare", [("PjitFunction(act)", 0.0, 0.6)])
    assert all(reader(bare) is None for reader in readers)
    # the parent's loop: the acting call and its fetch inside the rollout, and no other span in or after it
    parent = [(n, a, b) for n, a, b in HOST if n in ("Time/env_interaction_time", "Rollout/act_call", "Rollout/action_fetch", "Time/train_time")]
    run = run_of("parent", parent)
    assert rollout.rollout_host_ms(run) is None and rollout.update_boundary_idle_ms(run) is None
    assert rollout.act_launch_ms(run) == pytest.approx(0.05, abs=1e-6)  # what the parent has, it reads
    assert rollout.idle_unspanned_share(run) > 0


def test_a_call_whose_execution_the_capture_lost_is_left_out(run_of):
    """No call is paired with the next call's execution."""
    from perfbench import harness

    run = run_of("a_cell")
    pd = xplane.load(xplane.find_xplane(harness.OUT / "trace" / "a_cell"))
    ev = rollout.events(pd)
    ev["acts"] = ev["acts"][1:]  # the first rollout's first execution is gone
    steps = rollout.acting_steps(ev)
    assert len(steps) == 3 and steps[0][1] == pytest.approx(5.1e-3)
    assert rollout.idle_seconds(ev, 0.0, 31.5e-3) == pytest.approx(1e-3 * (31.5 - BUSY_MS))
    assert run["traced"]
