"""Each cell's traffic, engine drive, metric readers and check, at a size
the CPU runs, through the functions ``bench/run.py`` calls."""
import json
import subprocess
import sys
import time

import jax
import pytest

import harness
import tiny

CELLS = [w["name"] for w in
         json.loads((tiny.ROOT / "BENCHMARK.json").read_text())["workloads"]]
DEVICE_METRICS = {"device_idle_share", "serve_step_device_ms",
                  "flash_decode_roofline"}


def _run(cell, trace, seed=2 ** 31 + 3):
    return harness.run(cell, seed, 2.0, trace, time.perf_counter(),
                       jax.devices()[:cell.chips], log=lambda m: None)


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal(name):
    cell = tiny.tiny_cell(name)
    out = _run(cell, False)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "check"
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == cell.chips
    traced = _run(cell, True)
    # a CPU trace has no device plane: no device number is reported
    assert not set(traced["metrics"]) & DEVICE_METRICS
    assert set(traced["metrics"]) <= {m["name"] for m in cell.per_layer}
    json.dumps(out), json.dumps(traced)


def test_run_refuses_without_a_tpu():
    p = subprocess.run([sys.executable, str(tiny.BENCH / "run.py"),
                        "--workload", CELLS[0], "--seed", "1", "--seconds",
                        "1", "--trace", "0"], capture_output=True, text=True,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
                       cwd=tiny.ROOT, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit):
        harness.peak_for("TPU v99")
    assert harness.peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9

