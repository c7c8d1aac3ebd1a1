"""chip_smoke.py on the CPU: it refuses to run without a TPU, its parent
stays off jax, and every stage body passes its own oracle comparison at a
tiny size — so chip time is not spent debugging Python.

The stage bodies run in-process here (sharing pytest's jax); the full
parent -> child flow at the same sizes is ``python chip_smoke.py --tiny``.
"""

from __future__ import annotations

import io
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

TINY_ENGINE = chip_smoke.TINY_ENGINE_SHAPE
TINY_TIER = chip_smoke.TINY_TIER_SHAPE


@pytest.fixture(autouse=True)
def _private_manifest(tmp_path, monkeypatch):
    # the stages record fresh traces beside the compile cache; the suite
    # must not grow the checkout's manifest
    monkeypatch.setenv(
        "KARMADA_TPU_TRACE_MANIFEST", str(tmp_path / "trace_manifest.json")
    )


def _run(*argv, cwd=REPO, **env):
    return subprocess.run(
        [sys.executable, *argv], cwd=cwd, capture_output=True, text=True,
        env={**os.environ, **env}, timeout=120,
    )


def test_refuses_a_cpu_naming_what_it_found():
    proc = _run("chip_smoke.py", JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert proc.stdout == "", "a refused run prints no result"
    assert "found platform 'cpu'" in proc.stderr


def test_refuses_to_run_without_the_package(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text()
    )
    proc = _run("chip_smoke.py", cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no karmada_tpu package" in proc.stderr


def test_last_stdout_line_is_exactly_ok_and_device(monkeypatch, capsys):
    # the parent's flow with the children faked: whoever runs the smoke
    # parses the LAST stdout line and accepts these keys and no others
    device = {"platform": "tpu", "device_kind": "TPU v5 lite",
              "device_count": 1, "jax": "j", "jaxlib": "jl", "libtpu": "lt"}

    def fake_stage(stage, timeout, **kw):
        if stage == "probe":
            return dict(device)
        if stage == "mesh":
            return {"stage": stage, "skipped": "1 device", **device}
        return {"stage": stage, "mismatches": 0, **device}

    monkeypatch.setattr(chip_smoke, "spawn_stage", fake_stage)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert chip_smoke.main() == 0
    summary, last = map(json.loads, capsys.readouterr().out.splitlines())
    assert last == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert summary["stages"] == {
        "engine": "ok", "kernels": "ok", "plane": "ok", "sidecar": "ok",
        "mesh": "skipped: 1 device"}
    assert summary["versions"] == {"jax": "j", "jaxlib": "jl", "libtpu": "lt"}

    def failing_stage(stage, timeout, **kw):
        if stage == "kernels":
            raise RuntimeError("stage kernels exited rc=1")
        return fake_stage(stage, timeout, **kw)

    monkeypatch.setattr(chip_smoke, "spawn_stage", failing_stage)
    assert chip_smoke.main() != 0
    assert capsys.readouterr().out == "", "a failed run prints no result"


def test_parent_module_imports_without_jax():
    proc = _run(
        "-c",
        "import sys, chip_smoke, bench\n"
        "assert 'jax' not in sys.modules, 'the parent touched jax'\n",
    )
    assert proc.returncode == 0, proc.stderr


def test_engine_stage_tiny():
    facts = chip_smoke.stage_engine(
        *TINY_ENGINE, numpy_rows=256, oracle_rows=32
    )
    b = TINY_ENGINE[0]
    assert facts["rows_on_fleet"] == f"{b}/{b}"
    assert facts["mismatches"] == 0
    assert facts["delta_rows_packed"] == b // 100
    assert facts["delta_rows_replayed"] == b - b // 100
    assert facts["native_fold"] == "loaded"
    # the mixed-policy batch: 2 generations x 512 rows against refimpl
    # (divider_np + spread), one slot a placement on both
    assert facts["policy_rows_checked"] == 1024
    assert facts["policy_slots"] == chip_smoke.N_POLICY_SLOTS
    assert facts["policy_rows_device_selected"] > 0
    # the two-term placements' rows, eviction tasks and all, had their term
    # chosen by the fleet table's kernel
    assert facts["failover_rows_device_chosen"] > 100


def test_kernels_stage_tiny():
    facts = chip_smoke.stage_kernels(*TINY_TIER)
    assert 0 < facts["quota"]["denied"] < TINY_TIER[0]
    assert facts["explain"]["oracle_checked"] > 0
    assert facts["preempt"]["victims"] > 0
    assert facts["warmup"]["failed"] == 0
    assert facts["warmup"]["compiled"] == facts["warmup"]["records"] > 0


def test_plane_stage_tiny():
    facts = chip_smoke.stage_plane(*TINY_TIER)
    assert facts["kernel_device_spans"] > 0
    assert facts["device_bytes_samples"] > 0


def test_sidecar_stage_tiny():
    # the "chip" of this rehearsal is the CPU: same processes, same wire
    facts = chip_smoke.stage_sidecar(*TINY_TIER, solver_platform="cpu")
    assert facts["solver_backend"] == "cpu"
    assert facts["rows_compared"] >= 2 * TINY_TIER[0]
    assert facts["mismatches"] == 0 and facts["sidecar_exit"] == 0


def test_mesh_stage_tiny():
    # conftest gives 8 virtual CPU devices
    facts = chip_smoke.stage_mesh(*TINY_ENGINE)
    assert facts["identical_to_single"]
    dense = facts["layout"]["dense"]
    assert dense["per_device_bytes"] * 4 == dense["total_bytes"]


def test_a_failed_check_raises():
    with pytest.raises(RuntimeError, match="chip_smoke check failed"):
        chip_smoke._check(False, "on purpose")


class _FakeProc:
    """Just enough Popen for localup.scrape_line."""

    args = ["fake-solver"]
    returncode = None

    def __init__(self, text: str):
        r, w = os.pipe()
        os.write(w, text.encode())
        os.close(w)
        self.stdout = io.open(r, "r")

    def poll(self):
        return self.returncode


def test_solver_backend_mismatch_raises():
    from karmada_tpu.localup import scrape_line, scrape_solver_backend

    # three startup lines written back to back: one read swallows them all
    text = ("solver listening on port 7\nmetrics listening on port 8\n"
            "solver backend cpu\n")
    proc = _FakeProc(text)
    assert scrape_line(proc, r"port (\d+)", timeout=5) == "7"
    assert scrape_line(proc, r"metrics listening on port (\d+)", 5) == "8"
    assert scrape_solver_backend(proc, "cpu", timeout=5) == "cpu"
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=tpu.*'cpu'"):
        scrape_solver_backend(_FakeProc(text), "tpu", timeout=5)
