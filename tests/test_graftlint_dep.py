"""graftlint dep tier: row-dependence certification (delta-safety) gate.

Mirror of test_graftlint_ir.py one tier up: the full dep grid over the
committed registry must certify clean (every kernel's ``row_coupled``
declaration present, agreeing across its surfaces, and never
contradicted by the analyzer's proof), inside the runtime budget, with
ZERO baselined entries. The seeded mutants (tests/ir_mutant_kernels.py)
then pin that IR006 fires in BOTH contradiction directions and IR007
fires on the PR 9 sharded-scan regression shape — a certifier that
stops firing fails here, never silently.
"""

from __future__ import annotations

import json
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.graftlint import ir as graft_ir  # noqa: E402
from tools.graftlint.ir import (  # noqa: E402
    ENTRY_POINTS,
    KernelEntry,
    KernelSpec,
    entries_for_changed,
)
from tools.graftlint.dep import (  # noqa: E402
    declared_row_coupled,
    delta_safe_registry,
    render_delta_safe_table,
    run_dep,
)

MUTANT_MODULE = "ir_mutant_kernels"
MUTANT_PATH = "tests/ir_mutant_kernels.py"

VEC = (((8,), "int32"),)
MESH_B2 = (("b", 2), ("c", 1))


def dep_entry(attr: str, in_shapes, *, statics=None, row_coupled=None,
              row_args=(), plane_args=()) -> KernelEntry:
    spec = KernelSpec("mutant", tuple(in_shapes), dict(statics or {}))
    return KernelEntry(
        name=attr, family="ops", module=MUTANT_MODULE, attr=attr,
        path=MUTANT_PATH, make_specs=lambda: [spec],
        row_coupled=row_coupled, row_args=tuple(row_args),
        plane_args=tuple(plane_args),
    )


# -- the tier-1 gate + runtime budget ---------------------------------------


@pytest.fixture(scope="module")
def full_run():
    t0 = time.perf_counter()
    result = run_dep(root=REPO, baseline="auto")
    return result, time.perf_counter() - t0


def test_full_grid_certifies_clean(full_run):
    result, _ = full_run
    assert result.checked_files >= 30, "dep trace grid shrank"
    assert not result.findings, (
        "dep findings on the committed kernels:\n"
        + "\n".join(f.render() for f in result.findings)
    )
    assert not result.baseline_errors
    assert not result.unused_baseline
    # the delta-safety gate ships with a CLEAN tree, not a grandfathered
    # one: no dep finding is ever baselined
    assert not result.baselined


def test_full_grid_runtime_budget(full_run):
    _, seconds = full_run
    # the abstract interpretation must stay cheap enough for tier-1 and
    # the pre-commit --all path: the whole grid (trace + analysis) in
    # seconds, not minutes
    assert seconds < 5.0, f"dep grid took {seconds:.2f}s (budget 5s)"


def test_every_registered_kernel_declares_row_coupled():
    # the coverage half of the contract: every entry point states the
    # delta-safety bit on EVERY surface, and the surfaces agree
    for name, entry in ENTRY_POINTS.items():
        decl = declared_row_coupled(entry)
        assert decl["registry"] is not None, (
            f"{name}: ENTRY_POINTS entry missing row_coupled"
        )
        assert decl["kernel"] is not None, (
            f"{name}: kernel function missing the row_coupled attribute"
        )
        assert bool(decl["kernel"]) == bool(decl["registry"]), name
        if entry.manifest_kernel:
            assert decl.get("prewarm") is not None, (
                f"{name}: prewarm._KERNELS missing its row_coupled value"
            )
            assert bool(decl["prewarm"]) == bool(decl["registry"]), name


# -- seeded mutants: IR006 must fire in BOTH directions ---------------------


def test_ir006_declared_independent_but_coupled():
    entry = dep_entry("ir006_hidden_cumsum", VEC,
                      row_coupled=False, row_args=(0,))
    result = run_dep(entries={entry.name: entry}, root=REPO, baseline=None)
    assert not result.ok
    assert {f.rule for f in result.findings} == {"IR006"}
    (f,) = result.findings
    assert f.path == MUTANT_PATH
    assert f.detail.startswith("declared-independent-but-coupled:"), f.detail
    assert "cum" in f.detail, f.detail


def test_ir006_declared_coupled_but_independent():
    entry = dep_entry(
        "ir006_decoupled", (((8,), "int32"), ((8,), "int32")),
        row_coupled=True, row_args=(0,),
    )
    result = run_dep(entries={entry.name: entry}, root=REPO, baseline=None)
    assert not result.ok
    assert {f.rule for f in result.findings} == {"IR006"}
    (f,) = result.findings
    assert f.detail == "declared-coupled-but-independent"


def test_ir006_missing_declaration_on_full_scope(monkeypatch):
    # full-scope-only negative (the GL003 precedent): an entry with NO
    # declaration at all only convicts on the unscoped run
    entry = dep_entry("ir006_hidden_cumsum", VEC, row_args=(0,))
    monkeypatch.setattr(graft_ir, "ENTRY_POINTS", {entry.name: entry})
    result = run_dep(root=REPO, baseline=None)
    details = {f.detail for f in result.findings}
    assert "missing-declaration" in details, details
    # ...and stays OFF the scoped (entries=) runs
    scoped = run_dep(entries={entry.name: entry}, root=REPO, baseline=None)
    assert "missing-declaration" not in {f.detail for f in scoped.findings}


def test_ir007_fires_on_unreplicated_sharded_scan():
    entry = dep_entry(
        "ir007_sharded_scan", VEC, statics={"mesh": MESH_B2},
        row_coupled=True, row_args=(0,),
    )
    result = run_dep(entries={entry.name: entry}, root=REPO, baseline=None)
    assert not result.ok
    rules = {f.rule for f in result.findings}
    assert rules == {"IR007"}, [f.render() for f in result.findings]
    (f,) = result.findings
    assert f.path == MUTANT_PATH
    assert f.detail.startswith("unreplicated-coupler:cum"), f.detail


def test_ir007_silent_on_single_device_variant():
    # the same coupler without a mesh static is an honest single-device
    # coupled kernel — IR007 is a SHARDED-variant discipline only
    entry = dep_entry("ir007_sharded_scan", VEC,
                      row_coupled=True, row_args=(0,))
    result = run_dep(entries={entry.name: entry}, root=REPO, baseline=None)
    assert result.ok, [f.render() for f in result.findings]


# -- changed-only scoping over the spec_deps import graph -------------------


def test_entries_for_changed_follows_spec_deps():
    scoped = entries_for_changed(["karmada_tpu/ops/quota.py"])
    # quota.py is the source of the quota kernels AND a declared spec
    # dep of preempt_select and the fleet solve family (the cap grid
    # feeds both); the dispense/divide/masks kernels never read it
    assert {"quota_admit", "quota_cluster_caps"} <= set(scoped)
    assert "preempt_select" in scoped
    assert "fleet_pass" in scoped
    assert "divide_replicas" not in scoped
    assert "masks.contains_all" not in scoped

    scoped = entries_for_changed(["karmada_tpu/ops/dispense.py"])
    assert "take_by_weight" in scoped  # own source file
    assert "divide_replicas" in scoped  # via spec_deps
    assert "masks.intersects" not in scoped

    assert entries_for_changed(["karmada_tpu/utils/store.py"]) == {}


# -- the delta-safe registry surface ----------------------------------------


@pytest.fixture(scope="module")
def safe_rows():
    return delta_safe_registry(REPO)


def test_delta_safe_registry_matches_contract(safe_rows):
    by_name = {r["name"]: r for r in safe_rows}
    assert set(by_name) == set(ENTRY_POINTS)
    for r in safe_rows:
        # delta_safe is EARNED: declared independent AND proven so
        assert r["delta_safe"] == (
            r["row_coupled"] is False and r["verdict"] == "independent"
        )
    # the anchor kernels of each class (pinned so a lattice regression
    # that degrades proofs to 'unproven' cannot pass silently). The
    # kernels the fleet table's delta sub-pass dispatches over a partial
    # batch are the delta route's eligibility contract: nothing checks
    # them at run time, so a change that loses a certificate fails here
    for name in (
        "divide_replicas", "take_by_weight_batch", "general_estimate",
    ):
        assert by_name[name]["delta_safe"] is True, name
    assert by_name["explain_pass"]["delta_safe"] is True
    assert by_name["quota_admit"]["verdict"] == "coupled"
    assert by_name["masks.first_fit_group"]["plane_coupled"] is True
    assert not by_name["quota_admit"]["delta_safe"]


#: a serving process with no ``tools`` package: the scheduler imports,
#: schedules a batch, then a churned copy of it, which takes the delta
#: route (its untouched rows replayed), and ``tools`` was never imported
_NO_TOOLS = """
import importlib.abc, sys
import numpy as np


class NoTools(importlib.abc.MetaPathFinder):
    def find_spec(self, name, path=None, target=None):
        if name == "tools" or name.startswith("tools."):
            raise ImportError("no tools package in a serving process")


sys.meta_path.insert(0, NoTools())
sys.modules.pop("tools", None)
from karmada_tpu.scheduler import BindingProblem, ClusterSnapshot
from karmada_tpu.scheduler.core import TensorScheduler
import karmada_tpu.scheduler.fleet  # noqa: F401
from karmada_tpu.utils.builders import dynamic_weight_placement
from karmada_tpu.utils.builders import synthetic_fleet
from karmada_tpu.utils.tracing import tracer

snap = ClusterSnapshot(synthetic_fleet(16, seed=7))
pl = dynamic_weight_placement()
first = [BindingProblem(key=f"b{i}", placement=pl, replicas=1 + i % 7,
                        requests={"cpu": 500}, gvk="apps/v1/Deployment")
         for i in range(300)]
engine = TensorScheduler(snap, mesh=False, trace_manifest="")
engine.fleet_threshold = 1
engine.schedule(first)
second = list(first)
for i in (3, 40, 41, 200):
    p = first[i]
    second[i] = BindingProblem(key=p.key, placement=pl,
                               replicas=p.replicas + 1,
                               requests=p.requests, gvk=p.gvk)
tracer.clear()
engine.schedule(second)
(root,) = [s for s in tracer.dump() if s["name"] == "scheduler.schedule"]
assert root["attrs"]["path"] == "delta", root["attrs"]
assert engine._fleet.last_breakdown["dirty_rows"] == 4
assert not any(m == "tools" or m.startswith("tools.") for m in sys.modules)
print("delta")
"""


def test_the_delta_route_engages_without_the_tools_package(tmp_path):
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    out = subprocess.run(
        [sys.executable, "-c", _NO_TOOLS], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().splitlines()[-1] == "delta"


def test_delta_safe_table_renders_every_kernel(safe_rows):
    table = render_delta_safe_table(REPO)
    assert table.splitlines()[0].startswith("| kernel ")
    for r in safe_rows:
        assert f"`{r['name']}`" in table


def test_docs_delta_safe_table_not_drifted():
    # the generated DEVELOPMENT.md table is drift-guarded the same way
    # as the env-flag/metric/span tables: regenerate, don't hand-edit
    sys.path.insert(0, str(REPO / "tools"))
    import docs_from_bench

    docs_from_bench.check_delta_safe_table()


# -- the CLI surface --------------------------------------------------------


def _lint(*argv):
    return subprocess.run(
        [sys.executable, "-m", "tools.graftlint", *argv],
        cwd=REPO, capture_output=True, text=True,
    )


def test_cli_all_merges_three_tiers():
    proc = _lint("--all", "--format", "json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["ok"] is True
    assert set(doc["tiers"]) == {"ast", "ir", "dep"}
    for name, tier in doc["tiers"].items():
        assert tier["tier"] == name
        assert tier["seconds"] >= 0.0
        assert tier["ok"] is True


def test_cli_dep_tier_json_tags_findings():
    proc = _lint("--dep", "--format", "json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["tier"] == "dep"
    assert doc["ok"] is True


def test_cli_tier_flags_mutually_exclusive():
    for combo in (("--ir", "--dep"), ("--ir", "--all"),
                  ("--dep", "--all")):
        proc = _lint(*combo)
        assert proc.returncode == 2, combo
        assert "mutually exclusive" in proc.stderr


def test_cli_all_refuses_path_scope():
    proc = _lint("--all", "karmada_tpu/ops/quota.py")
    assert proc.returncode == 2
    assert "--changed-only" in proc.stderr
