"""A new traffic mix and a new per-layer metric are files plus entries in
BENCHMARK.json: no file of the harness changes."""
import json
import shutil
import time

import jax

import harness
import tiny


def test_dummy_mix_and_metric_from_files_alone(tmp_path):
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((tiny.ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "traffic" / "dummy_mix.json").write_text(json.dumps(
        {"kind": "sessions", "contexts": [40, 24], "max_new_tokens": 300,
         "max_batch": 2, "pool_tokens": 1024, "warm_steps": 1,
         "check_tokens": 8}))
    (tmp_path / "bench" / "metrics" / "dummy_steps.py").write_text(
        "def read(ctx):\n    return float(len(ctx.steps)) or None\n")
    spec["workloads"].append({"name": "granite-3-2b.dummy",
                              "config": "granite-3-2b",
                              "traffic": "dummy_mix", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "dummy_steps", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "engine", "moves": "ttl_p50_ms",
                              "workloads": ["granite-3-2b.dummy"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = tiny.tiny_cell("granite-3-2b.dummy", root=tmp_path,
                          contexts=[40, 24], pool_tokens=1024)
    assert cell.bench == tmp_path / "bench"
    out = harness.run(cell, 3, 1.0, True, time.perf_counter(),
                      jax.devices()[:1], log=lambda m: None)
    assert out["metrics"]["dummy_steps"]["value"] > 0
    assert out["metrics"]["dummy_steps"]["unit"] == "steps"
