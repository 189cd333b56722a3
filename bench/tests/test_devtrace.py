"""The trace reduction, on a hand-made trace and on a recorded one."""
import json
import pathlib
import types

import pytest

import devtrace

RECORDED = pathlib.Path(__file__).resolve().parent / "data"


def _hand_made():
    # one device, window [0, 100): a loop 5-45 holding ops 10-30 and
    # 20-40, a kernel 60-70 and a copy 65-80 beside it; no host span
    # open over 50-55
    ops = {"/device:TPU:0": [("%fusion.1 = f32[2] fusion()", 10, 30),
                             ("%fusion.2 = f32[2] fusion()", 20, 40),
                             ("%while.3 = (s32[]) while()", 5, 45),
                             ("%flash_decode.1 = (bf16[1]) custom-call()", 60, 70),
                             ("%copy.3 = bf16[4] copy()", 65, 80)]}
    mods = {"/device:TPU:0": [("jit_serve_step(1)", 10, 80)]}
    spans = [("engine.step", 0, 50), ("engine.step", 55, 100)]
    return devtrace.Trace(ops, mods, spans)


def test_busy_gaps_and_labels():
    t = _hand_made()
    assert t.window() == (0, 100)
    assert devtrace.busy_ns(t, "/device:TPU:0", 0, 100) == 40 + 20
    assert devtrace.gaps(t, "/device:TPU:0", 0, 100) == [
        (0, 5), (45, 60), (80, 100)]
    assert devtrace.span_at(t, 52) == "outside the harness's spans"
    b = devtrace.breakdown(t)
    assert b["device_ops"][0] == ["fusion", 40e-9]
    assert devtrace.op_kind("%flash_decode.2 = (bf16[1]) x") == "flash_decode"
    assert len(devtrace.leaf_ops(t.ops["/device:TPU:0"])) == 4
    assert b["idle_gaps"][0][1] == pytest.approx(20e-9)
    assert sorted(b["idle_gaps"]) == sorted([["engine.step", 5e-9],
                                             ["outside the harness's spans", 15e-9],
                                             ["engine.step", 20e-9]])


def test_json_round_trip(tmp_path):
    t = _hand_made()
    t.save(tmp_path / "t.json.gz")
    u = devtrace.load_json(tmp_path / "t.json.gz")
    assert u.ops == t.ops and u.spans == t.spans and u.modules == t.modules


def test_reads_a_cpu_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("engine.step"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = devtrace.load(devtrace.find_xplane(tmp_path), ("engine.step",))
    assert [s[0] for s in t.spans] == ["engine.step"]
    assert t.ops == {}            # the CPU has no device plane


@pytest.mark.skipif(not (RECORDED / "long_decode_trace.json.gz").exists(),
                    reason="no recorded chip trace")
def test_recorded_chip_trace():
    """A stretch of a chip trace of the long-decode cell: op names, busy
    time and the metric readers' kernel patterns."""
    t = devtrace.load_json(RECORDED / "long_decode_trace.json.gz")
    meta = json.loads((RECORDED / "long_decode_trace.meta.json").read_text())
    lo, hi = t.window()
    dev = t.devices[0]
    busy = devtrace.busy_ns(t, dev, lo, hi)
    assert busy == meta["busy_ns"]
    assert 0 < busy < hi - lo
    ctx = types.SimpleNamespace(trace=t)
    import readings
    assert readings.op_time(ctx, r"^flash_decode$") == pytest.approx(
        meta["decode_kernel_s"])
    assert len(readings.program_calls(ctx, r"serve_step")) == \
        meta["serve_step_calls"]
