"""graftlint IR tier: tier-1 gate + seeded-mutant fixture corpus.

The gate: every registered kernel entry point abstractly traces across
its bucket grid and the IR001-IR005 invariants hold with ZERO
non-baselined findings. The mutant tests register intentionally-defective
kernels (tests/ir_mutant_kernels.py) as temporary entries and assert each
rule fires and fails the gate — a rule can never silently stop firing.

Everything here runs on the conftest CPU platform; tracing is abstract
(jax.make_jaxpr over ShapeDtypeStructs — no compiles, no data), so the
full grid audits in a few seconds.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.graftlint import ir as graft_ir  # noqa: E402
from tools.graftlint.ir import (  # noqa: E402
    ENTRY_POINTS,
    KernelEntry,
    KernelSpec,
    run_ir,
)

MUTANT_MODULE = "ir_mutant_kernels"
MUTANT_PATH = "tests/ir_mutant_kernels.py"


def mutant_entry(attr: str, in_shapes, *, path=MUTANT_PATH, statics=None,
                 manifest=None) -> KernelEntry:
    spec = KernelSpec("mutant", tuple(in_shapes), dict(statics or {}))
    return KernelEntry(
        name=attr, family="ops", module=MUTANT_MODULE, attr=attr,
        path=path, make_specs=lambda: [spec], manifest_kernel=manifest,
    )


VEC = (((8,), "int32"),)


# -- the tier-1 gate ---------------------------------------------------------


@pytest.fixture(scope="module")
def full_result():
    return run_ir(root=REPO, baseline="auto")


def test_full_grid_zero_findings(full_result):
    assert full_result.checked_files >= 20, "bucket grid shrank"
    assert not full_result.findings, (
        "IR findings on the committed kernels:\n"
        + "\n".join(f.render() for f in full_result.findings)
    )
    assert not full_result.baseline_errors
    assert not full_result.unused_baseline


def test_registry_covers_exports_and_fleet():
    # ops exports <-> IR registry (the docs drift gate's invariant)
    unregistered, stale = graft_ir.ops_registry_drift(REPO)
    assert not unregistered and not stale, (unregistered, stale)
    # every entry builds at least one spec, and the manifest-capable set
    # matches prewarm's kernel list exactly
    from karmada_tpu.scheduler import prewarm

    manifest_capable = set()
    for entry in ENTRY_POINTS.values():
        assert entry.make_specs(), f"{entry.name} has an empty spec grid"
        if entry.manifest_kernel:
            manifest_capable.add(entry.manifest_kernel)
    assert manifest_capable == set(prewarm._KERNELS)
    assert set(prewarm._jit_registry()) == set(prewarm._KERNELS)


# -- seeded mutants: each rule must fire and fail the gate -------------------


MUTANTS = {
    "IR001": mutant_entry("ir001_weak_promotion", VEC),
    "IR002": mutant_entry("ir002_host_callback", VEC),
    "IR003": mutant_entry("ir003_const_capture", VEC),
    "IR005": mutant_entry(
        "ir005_dropped_donation", (((4,), "int32"), ((8,), "int32"))
    ),
    # silently-UN-donated variants: the donated invar HAS a plausible
    # consumer, but a reshape/astype at the kernel boundary leaves no
    # identically-shaped output to alias into — exactly how a refactor
    # quietly doubles the resident's HBM footprint
    "IR005-reshape": mutant_entry(
        "ir005_reshaped_donation", (((8,), "int32"), ((8,), "int32"))
    ),
    "IR005-astype": mutant_entry(
        "ir005_astype_donation", (((8,), "int32"), ((8,), "int32"))
    ),
}


@pytest.mark.parametrize("rule_id", sorted(MUTANTS))
def test_mutant_fires_and_fails_gate(rule_id):
    entry = MUTANTS[rule_id]
    rule = rule_id.split("-")[0]
    result = run_ir(entries={entry.name: entry}, root=REPO, baseline=None)
    assert not result.ok, f"{rule_id} mutant passed the gate"
    hits = [f for f in result.findings if f.rule == rule]
    assert hits, f"{rule_id} did not fire on its mutant"
    assert all(f.path == MUTANT_PATH for f in hits)
    others = [f for f in result.findings if f.rule != rule]
    assert not others, [f.render() for f in others]


def test_sharded_specs_cover_fleet_kernels():
    # the sharded grid contract (ISSUE 9): every mesh-parameterized entry
    # point traces under a >=2-device spec, so IR001-IR005 — including
    # the donation audit over the row-sharded residents — cover the
    # PARTITIONED executables, not just the single-device forms
    for name in ("fleet_pass", "fleet_entries"):
        variants = {s.variant: s for s in ENTRY_POINTS[name].make_specs()}
        spec = variants.get("sharded-b2")
        assert spec is not None, f"{name} lost its sharded spec"
        assert spec.statics.get("mesh") == (("b", 2), ("c", 1))


def test_one_kernel_set_on_every_declaring_surface():
    # one kernel, several declaration sites (ROADMAP Queue 3 item 6):
    # the registry the engine dispatches from, prewarm's jax-free mirror
    # and the lint's manifest-bearing entry points name the same nine
    # kernels (ten since fleet_quota), and the trace-key families that feed the compile counter
    # name no kernel beyond them and the three ledger-only utilities
    from karmada_tpu.scheduler import fleet, prewarm

    want = {
        "fleet_pass", "fleet_entries", "fleet_bits", "fleet_select",
        "fleet_terms", "fleet_quota",
        "quota_admit", "quota_cluster_caps", "explain_pass",
        "preempt_select",
    }
    assert set(fleet.FLEET_KERNELS) == want
    assert set(prewarm._KERNELS) == want
    assert {
        e.manifest_kernel for e in ENTRY_POINTS.values()
        if e.manifest_kernel
    } == want
    assert set(prewarm._KEY_CAP_INDEX) <= want
    assert set(fleet._TRACE_KERNELS.values()) - want == {
        "state_scatter", "meta_gather", "estimate_fold",
    }


def test_ir001_detail_names_dtype_and_primitive():
    entry = MUTANTS["IR001"]
    result = run_ir(entries={entry.name: entry}, root=REPO, baseline=None)
    details = {f.detail for f in result.findings}
    assert any(d.startswith("float64:") for d in details), details


def test_ir004_trace_drift_fires():
    # a registry spec that no longer matches the kernel signature IS the
    # IR004 finding (the drift that would break prewarm replay)
    entry = mutant_entry("ir002_host_callback", (((8,), "int32"),) * 3)
    result = run_ir(entries={entry.name: entry}, root=REPO, baseline=None)
    assert not result.ok
    assert [f.rule for f in result.findings] == ["IR004"]
    assert result.findings[0].detail.startswith("trace:")


def test_ir004_registry_coverage_drift(monkeypatch):
    from karmada_tpu.scheduler import prewarm

    monkeypatch.setattr(
        prewarm, "_KERNELS", tuple(
            k for k in prewarm._KERNELS if k != "fleet_bits"
        ),
    )
    result = run_ir(root=REPO, baseline=None)
    hits = [
        f for f in result.findings
        if f.rule == "IR004" and f.detail == "coverage:fleet_bits"
    ]
    assert hits and not result.ok
    assert any("prewarm" in f.message for f in hits)


# -- manifest fidelity (IR004 over a live manifest) --------------------------


FLEET_FAMILIES = ["fleet_pass", "fleet_entries", "fleet_bits"]


@pytest.fixture(scope="module")
def toy_manifest(tmp_path_factory):
    """A real recorded manifest: one engine, toy shapes, 2 passes."""
    from test_compile_lifecycle import seed_manifest

    path = tmp_path_factory.mktemp("irmanifest") / "manifest.json"
    seed_manifest(path)
    return path


def test_manifest_records_audit_clean(toy_manifest):
    result = run_ir(
        FLEET_FAMILIES, root=REPO, baseline=None,
        manifest=str(toy_manifest),
    )
    assert result.ok, [f.render() for f in result.findings]


def test_manifest_corrupt_record_fires_ir004(toy_manifest, tmp_path):
    data = json.loads(toy_manifest.read_text())
    assert data["records"], "toy manifest recorded nothing"
    data["records"][0]["in_shapes"] = data["records"][0]["in_shapes"][:-1]
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(data))
    result = run_ir(
        FLEET_FAMILIES, root=REPO, baseline=None, manifest=str(bad)
    )
    assert not result.ok
    assert any(
        f.rule == "IR004" and "trace-failed" in f.detail
        for f in result.findings
    )


def test_manifest_unknown_kernel_fires_ir004(toy_manifest):
    # audit with a registry that lacks the recorded families entirely:
    # every record must surface as unknown-kernel, not silently skip
    entry = MUTANTS["IR002"]
    result = run_ir(
        entries={entry.name: entry}, root=REPO, baseline=None,
        manifest=str(toy_manifest),
    )
    assert any(
        f.rule == "IR004" and "unknown-kernel" in f.detail
        for f in result.findings
    )


def test_manifest_missing_or_empty_is_a_finding(tmp_path):
    # an explicitly-audited manifest that is unreadable or holds zero
    # records must FAIL the audit, never report clean — the operator
    # asked to prove prewarm coverage and there is none
    entry = MUTANTS["IR002"]
    absent = run_ir(
        entries={entry.name: entry}, root=REPO, baseline=None,
        manifest=str(tmp_path / "absent.json"),
    )
    assert not absent.ok
    assert any(f.detail == "manifest:unreadable" for f in absent.findings)

    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"version": 1, "records": []}))
    res = run_ir(
        entries={entry.name: entry}, root=REPO, baseline=None,
        manifest=str(empty),
    )
    assert not res.ok
    assert any(f.detail == "manifest:empty" for f in res.findings)


def test_manifest_removed_family_records_surface(tmp_path):
    # the audit parses the manifest RAW: records for a kernel family the
    # build no longer knows (renamed/removed — prewarm's loader would
    # silently drop them) must surface as unknown-kernel findings
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps({
        "version": 1,
        "records": [{
            "kernel": "fleet_bits_old", "key": None,
            "in_shapes": [[[4], "int32"]], "statics": {},
        }],
    }))
    result = run_ir(
        FLEET_FAMILIES, root=REPO, baseline=None, manifest=str(stale)
    )
    assert not result.ok
    assert any(
        f.rule == "IR004" and "unknown-kernel" in f.detail
        for f in result.findings
    )


def test_manifest_canon_drift_fires_ir004(tmp_path):
    # a record whose serialized form does not survive prewarm's own
    # save/load writers (float dims here) must be flagged even though it
    # traces fine — replay dedup and ledger seeding key on the canon
    entry = mutant_entry(
        "ir002_host_callback", VEC, manifest="toykernel"
    )
    manifest = tmp_path / "drift.json"
    manifest.write_text(json.dumps({
        "version": 1,
        "records": [{
            "kernel": "toykernel", "key": None,
            "in_shapes": [[[8.0], "int32"]], "statics": {},
        }],
    }))
    from karmada_tpu.scheduler import prewarm

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(prewarm, "_KERNELS", ("toykernel",))
        result = run_ir(
            entries={entry.name: entry}, root=REPO, baseline=None,
            manifest=str(manifest),
        )
    drift = [
        f for f in result.findings
        if f.rule == "IR004" and "canon-drift" in f.detail
    ]
    assert drift, [f.render() for f in result.findings]


# -- suppression + baseline share the AST tier's machinery -------------------


def test_def_line_suppression(tmp_path):
    mod = tmp_path / "ir_suppress_mutant.py"
    mod.write_text(textwrap.dedent(
        """
        import jax

        def suppressed_callback(x):  # graftlint: disable=IR002
            return jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x
            )
        """
    ))
    sys.path.insert(0, str(tmp_path))
    try:
        entry = KernelEntry(
            name="suppressed_callback", family="ops",
            module="ir_suppress_mutant", attr="suppressed_callback",
            path="ir_suppress_mutant.py",
            make_specs=lambda: [KernelSpec("m", VEC)],
        )
        result = run_ir(
            entries={entry.name: entry}, root=tmp_path, baseline=None
        )
    finally:
        sys.path.remove(str(tmp_path))
    assert not result.findings
    assert result.suppressed_count == 1


def test_baseline_grandfathers_ir_findings(tmp_path):
    entry = MUTANTS["IR002"]
    raw = run_ir(entries={entry.name: entry}, root=REPO, baseline=None)
    assert raw.findings
    (tmp_path / "bl.json").write_text(json.dumps({
        "version": 1,
        "entries": [
            {"rule": f.rule, "path": f.path, "anchor": f.anchor,
             "detail": f.detail,
             "justification": "fixture: grandfathered for the test"}
            for f in raw.findings
        ],
    }))
    result = run_ir(
        entries={entry.name: entry}, root=tmp_path, baseline="bl.json"
    )
    assert result.ok
    assert len(result.baselined) == len(raw.findings)


# -- parity: the single-sourced accumulator dtypes ---------------------------


def test_acc_dtype_parity():
    from karmada_tpu.ops import dispense
    from karmada_tpu.refimpl import divider_np

    assert np.dtype(dispense.ACC_WIDE) == np.dtype(divider_np.ACC_NP)
    assert np.dtype(dispense.ACC_WIDE) == np.dtype(np.int64)
    assert np.dtype(dispense.ACC_NARROW) == np.dtype(np.int32)
    assert dispense.acc_dtype(True) is dispense.ACC_WIDE
    assert dispense.acc_dtype(False) is dispense.ACC_NARROW


# -- surfaces: module CLI, karmadactl verb, docs drift gate ------------------


def test_module_cli_ir_json():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--ir",
         "merge_estimates", "--format", "json"],
        cwd=REPO, capture_output=True, text=True, timeout=180,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] is True
    assert payload["checked_files"] >= 1


def test_cli_lint_ir_verb(capsys):
    from karmada_tpu import cli

    rc = cli.main(["lint", "--ir", "merge_estimates", "--format", "json"])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["ok"] is True


def test_cli_ir_unknown_family_is_usage_error():
    from karmada_tpu import cli

    rc = cli.main(["lint", "--ir", "no_such_kernel"])
    assert rc == 2


def test_cli_empty_manifest_is_usage_error(capsys):
    # `--manifest "$KARMADA_TPU_TRACE_MANIFEST"` with the var unset must
    # never silently skip the audit the operator asked for
    from karmada_tpu import cli

    rc = cli.main(["lint", "--ir", "--manifest", ""])
    assert rc == 2
    assert "KARMADA_TPU_TRACE_MANIFEST" in capsys.readouterr().err


def test_write_baseline_refuses_partial_scope():
    from tools.graftlint.__main__ import main as graftlint_main

    rc = graftlint_main(["--write-baseline", "--changed-only"])
    assert rc == 2


def test_changed_only_scope(tmp_path):
    from tools.graftlint.__main__ import changed_py_files

    def git(*args):
        subprocess.run(
            ["git", *args], cwd=tmp_path, check=True, capture_output=True,
            env={"GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
                 "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t",
                 "HOME": str(tmp_path), "PATH": "/usr/bin:/bin:/usr/local/bin"},
        )

    git("init", "-q")
    (tmp_path / "committed.py").write_text("A = 1\n")
    (tmp_path / "notes.md").write_text("x\n")
    git("add", "-A")
    git("commit", "-q", "-m", "seed")
    (tmp_path / "committed.py").write_text("A = 2\n")  # modified
    (tmp_path / "fresh.py").write_text("B = 1\n")  # untracked
    assert changed_py_files(tmp_path) == ["committed.py", "fresh.py"]


def test_ops_export_drift_fails_docs_regen(monkeypatch):
    sys.path.insert(0, str(REPO / "tools"))
    import docs_from_bench

    docs_from_bench.check_ir_registry()  # clean on the committed tree

    pruned = {
        name: e for name, e in ENTRY_POINTS.items()
        if e.name != "divide_replicas"
    }
    monkeypatch.setattr(graft_ir, "ENTRY_POINTS", pruned)
    with pytest.raises(SystemExit, match="divide_replicas"):
        docs_from_bench.check_ir_registry()
