"""jaxlint-IR: the jaxpr/HLO audit tier (``sheeprl_tpu/analysis/ir``).

Rule-level tests build tiny synthetic jitted programs; the CLI tests inject REAL
violations — an un-donated buffer (IR001) and a compile-memory budget inflation
(IR006) — through a monkeypatched registry and assert the non-zero exit the CI
job relies on.  The audit of the actual entry points runs in ``test_e2e.py``
(one cheap entry in tier 1, the full registry as a slow test + the CI job).
"""

from __future__ import annotations

import json
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.analysis.ir import budgets as budgets_mod
from sheeprl_tpu.analysis.ir import entrypoints as entrypoints_mod
from sheeprl_tpu.analysis.ir.__main__ import main as ir_main
from sheeprl_tpu.analysis.ir.rules import (
    check_callbacks,
    check_collectives,
    check_constants,
    check_donation,
    check_dtype_promotion,
    lower_entry,
    measured_budget,
)
from sheeprl_tpu.analysis.ir.types import AuditEntry


def _entry(fn, args, **kw):
    return AuditEntry(name=kw.pop("name", "test/entry"), fn=fn, args=args, **kw)


# ------------------------------------------------------------------------ IR001
def test_ir001_flags_unaliased_donated_buffer():
    def f(big, y):
        return big.sum() + y  # no output can reuse big's (64, 64) buffer

    fn = jax.jit(f, donate_argnums=(0,))
    art = lower_entry(_entry(fn, (jnp.zeros((64, 64)), jnp.zeros(()))))
    findings = check_donation(art)
    assert [f.rule for f in findings] == ["IR001"]
    assert "NOT aliased" in findings[0].message


def test_ir001_clean_when_donation_applies():
    def f(x, y):
        return x * 2 + y

    fn = jax.jit(f, donate_argnums=(0,))
    art = lower_entry(_entry(fn, (jnp.zeros((64, 64)), jnp.zeros((64, 64)))))
    assert check_donation(art) == []


def test_ir001_scalar_slack_tolerated():
    # A refreshed scalar counter (the Anakin episode-sum pattern) stays under the
    # slack; the same shortfall above the slack threshold fires.
    def f(counter, x):
        return jnp.zeros(()), x * 2

    fn = jax.jit(f, donate_argnums=(0,))
    art = lower_entry(_entry(fn, (jnp.zeros(()), jnp.zeros((8,)))))
    assert check_donation(art) == []
    assert check_donation(art, slack_bytes=0) != []


# ------------------------------------------------------------------------ IR002
def test_ir002_flags_f32_dot_under_declared_bf16():
    def f(a, b):
        return a @ b

    a = jnp.zeros((8, 8), jnp.float32)
    art = lower_entry(_entry(jax.jit(f), (a, a), precision="bf16-mixed"))
    findings = check_dtype_promotion(art)
    assert [f.rule for f in findings] == ["IR002"]
    assert "float32" in findings[0].message


def test_ir002_clean_for_bf16_dot_and_declared_fp32():
    def f(a, b):
        return a @ b

    bf = jnp.zeros((8, 8), jnp.bfloat16)
    art = lower_entry(_entry(jax.jit(f), (bf, bf), precision="bf16-mixed"))
    assert check_dtype_promotion(art) == []
    f32 = jnp.zeros((8, 8), jnp.float32)
    art = lower_entry(_entry(jax.jit(f), (f32, f32), precision="fp32"))
    assert check_dtype_promotion(art) == []


# ------------------------------------------------------------------------ IR003
def _scan_with_callback():
    def f(x):
        def body(c, _):
            jax.debug.callback(lambda v: None, c)
            return c + 1, c

        out, _ = jax.lax.scan(body, x, None, length=4)
        return out

    return jax.jit(f)


def test_ir003_flags_callback_inside_scan():
    art = lower_entry(_entry(_scan_with_callback(), (jnp.zeros(()),)))
    findings = check_callbacks(art)
    assert [f.rule for f in findings] == ["IR003"]
    assert "scan/while" in findings[0].message


def test_ir003_gate_and_top_level_callback_are_clean():
    art = lower_entry(_entry(_scan_with_callback(), (jnp.zeros(()),), callbacks_gated=True))
    assert check_callbacks(art) == []

    def g(x):
        jax.debug.callback(lambda v: None, x)  # hot-loop rule only: top level ok
        return x + 1

    art = lower_entry(_entry(jax.jit(g), (jnp.zeros(()),)))
    assert check_callbacks(art) == []


# ------------------------------------------------------------------------ IR004
def test_ir004_flags_collective_in_single_mesh_graph():
    from sheeprl_tpu.parallel.mesh import build_mesh
    from jax.sharding import PartitionSpec as P

    mesh = build_mesh(devices=jax.devices()[:1])

    def f(x):
        return jax.shard_map(
            lambda v: jax.lax.psum(v, "data"), mesh=mesh, in_specs=(P("data"),), out_specs=P()
        )(x)

    art = lower_entry(_entry(jax.jit(f), (jnp.zeros((8,)),)))
    findings = check_collectives(art)
    assert [f.rule for f in findings] == ["IR004"]
    assert "psum" in findings[0].message
    # a multi-mesh entry declares single_mesh=False and is exempt
    art = lower_entry(_entry(jax.jit(f), (jnp.zeros((8,)),), single_mesh=False))
    assert check_collectives(art) == []


# ------------------------------------------------------------------------ IR005
def test_ir005_flags_oversize_baked_constant():
    baked = jnp.asarray(np.zeros((64, 1024), np.float32))  # 256 KiB closure const

    def f(x):
        return (x * baked).sum()

    art = lower_entry(_entry(jax.jit(f), (jnp.zeros((1024,)),)))
    findings = check_constants(art, max_const_bytes=128 * 1024)
    assert [f.rule for f in findings] == ["IR005"]
    assert check_constants(art, max_const_bytes=1024 * 1024) == []


# ------------------------------------------------------------------------ IR006
def test_ir006_budget_drift_unit():
    measured = {"a": {"total_bytes": 1000}, "new": {"total_bytes": 10}}
    baseline = {
        "meta": {"tolerance": 0.25, "abs_slack_bytes": 0},
        "entries": {"a": {"total_bytes": 500}, "gone": {"total_bytes": 5}},
    }
    findings = budgets_mod.check_budgets(measured, baseline)
    details = sorted(f.detail for f in findings)
    assert details == ["budget-exceeded", "no-budget-row", "stale-budget-row"]
    # within tolerance: no drift finding
    ok = budgets_mod.check_budgets({"a": {"total_bytes": 600}}, baseline)
    assert [f.detail for f in ok] == ["no-budget-row", "stale-budget-row"] or all(
        f.detail != "budget-exceeded" for f in ok
    )
    assert budgets_mod.check_budgets({"a": {"total_bytes": 1}}, None)[0].detail == "missing-baseline"


# ------------------------------------------------------------- CLI (exit codes)
HOOKS_MODULE = """
import jax
import jax.numpy as jnp

from sheeprl_tpu.analysis.ir.types import AuditEntry


def good():
    def f(x, y):
        return x * 2 + y

    fn = jax.jit(f, donate_argnums=(0,))
    z = jnp.zeros((32, 32))
    return [AuditEntry(name="good/entry", fn=fn, args=(z, z), covers=("good",))]


def bad_donation():
    def f(big, y):
        return big.sum() + y  # the donated (64, 64) buffer backs NO output

    fn = jax.jit(f, donate_argnums=(0,))
    return [AuditEntry(name="bad/entry", fn=fn, args=(jnp.zeros((64, 64)), jnp.zeros(())), covers=("bad",))]
"""


@pytest.fixture()
def synthetic_registry(tmp_path, monkeypatch):
    """Point the audit registry at a synthetic hooks module in tmp_path; returns
    a function selecting which hooks the registry exposes."""
    (tmp_path / "ir_synthetic_hooks.py").write_text(textwrap.dedent(HOOKS_MODULE))
    monkeypatch.syspath_prepend(str(tmp_path))
    monkeypatch.chdir(tmp_path)

    def select(**hooks):
        registry = {name: f"ir_synthetic_hooks:{fn}" for name, fn in hooks.items()}
        monkeypatch.setattr(entrypoints_mod, "REGISTRY", registry)
        monkeypatch.setattr(entrypoints_mod, "EXPECTED_COVERAGE", frozenset(hooks))
        return registry

    return select


def test_cli_clean_registry_exits_zero(synthetic_registry, capsys):
    synthetic_registry(good="good")
    assert ir_main(["--write-budgets", "-q"]) == 0
    assert ir_main(["-q"]) == 0


def test_cli_ir001_real_violation_nonzero_exit(synthetic_registry, capsys):
    """Acceptance: a REAL un-donated buffer (donate_argnums the compiled HLO does
    not alias) makes the audit exit non-zero."""
    synthetic_registry(bad="bad_donation")
    assert ir_main(["--write-budgets", "-q"]) == 0  # budgets green; IR001 is the finding
    rc = ir_main(["-q"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "IR001" in out


def test_cli_ir006_budget_inflation_nonzero_exit(synthetic_registry, tmp_path, capsys):
    """Acceptance: a compile-memory budget inflation past the tolerance makes the
    audit exit non-zero (baseline shrunk 10x == program grew 10x)."""
    synthetic_registry(good="good")
    assert ir_main(["--write-budgets", "-q"]) == 0
    doc = json.loads((tmp_path / "irbudgets.json").read_text())
    for row in doc["entries"].values():
        for k in row:
            row[k] = max(row[k] // 10, 1)
    (tmp_path / "irbudgets.json").write_text(json.dumps(doc))
    rc = ir_main(["-q"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "IR006" in out and "budget exceeded" in out


def test_cli_coverage_floor_fails_closed(synthetic_registry, capsys):
    synthetic_registry(good="good")
    ir_main(["--write-budgets", "-q"])
    # the floor demands an entry point no hook covers anymore -> IR000
    entrypoints_mod.EXPECTED_COVERAGE = frozenset({"good", "vanished"})
    rc = ir_main(["-q"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "IR000" in out and "vanished" in out


def test_cli_list_and_unknown_entry(synthetic_registry, capsys):
    synthetic_registry(good="good")
    assert ir_main(["--list"]) == 0
    assert "good/entry" in capsys.readouterr().out
    assert ir_main(["--entry", "nope"]) == 2


def test_measured_budget_reports_alias_bytes():
    def f(x, y):
        return x * 2 + y

    fn = jax.jit(f, donate_argnums=(0,))
    z = jnp.zeros((32, 32))
    art = lower_entry(_entry(fn, (z, z)))
    budget = measured_budget(art)
    assert budget["alias_bytes"] == z.size * 4
    assert budget["total_bytes"] >= budget["temp_bytes"]
