"""``combine_exposed_ms`` on hand-made traces, and the four-chip cell as
``load_cell`` resolves it."""
import types

import pytest

import devtrace
import harness
import progtrace
import tiny

US = 1000
SCOPES = progtrace.program_names()[1]
A2A = "jit(serve_step)/while/body/closed_call/attn/combine/all_to_all"
ATTN = "jit(serve_step)/while/body/closed_call/attn/jit(flash_decode)/x"
FFN = "jit(serve_step)/while/body/closed_call/ffn/dot_general"


def _reader():
    cell = harness.load_cell(tiny.ROOT, "granite-8b-kvp4.long_decode")
    return harness.load_module(cell.bench / "metrics"
                               / "combine_exposed_ms.py")


def _ctx(ops_by_dev, modules_by_dev):
    trace = devtrace.Trace({d: [o[:3] for o in ops]
                            for d, ops in ops_by_dev.items()},
                           modules_by_dev, [("engine.step", 0, 1000 * US)])
    pt = progtrace.ProgramTrace(ops_by_dev, [], SCOPES)
    return types.SimpleNamespace(trace=trace, program_trace=pt)


def _step(x0):
    """One serve-step execution of 300 us at ``x0``: a combine op of 20
    us under an attention op and an FFN op that overlap it end to end
    (hidden), an exposed one of 30 us, and an async all-to-all whose
    start (5 us) and done (15 us) flank 95 us of attention: the time
    between them does not count, the start and the done do."""
    u = lambda a, b: (x0 + a * US, x0 + b * US)              # noqa: E731
    return [
        ("%fusion.1 = bf16[8] fusion()", *u(0, 50), ATTN),
        ("%all-to-all.1 = bf16[8] all-to-all()", *u(40, 60), A2A),
        ("%fusion.4 = bf16[8] fusion()", *u(45, 100), FFN),
        ("%all-to-all.2 = bf16[8] all-to-all()", *u(100, 130), A2A),
        ("%all-to-all-start.1 = bf16[8] all-to-all-start()", *u(130, 135),
         A2A),
        ("%fusion.2 = bf16[8] fusion()", *u(135, 230), ATTN),
        ("%all-to-all-done.1 = bf16[8] all-to-all-done()", *u(230, 245),
         A2A),
        ("%fusion.3 = s32[4] fusion()", *u(245, 300),
         "jit(serve_step)/sample/argmax"),
    ]


def test_hidden_exposed_and_async_pair():
    ops = _step(100 * US)
    ctx = _ctx({"/device:TPU:0": ops},
               {"/device:TPU:0": [("jit_serve_step(3)", 100 * US,
                                   400 * US)]})
    assert _reader().read(ctx) == pytest.approx(0.030 + 0.005 + 0.015)


def test_mean_over_executions_and_devices():
    """Two devices, two executions each: a device whose combine is all
    hidden reads 0 and halves the mean; an execution of another program
    and one centred outside the window are not read."""
    hidden = _step(0)[:3]
    shift = lambda ops, d: [(n, s + d, e + d, o) for n, s, e, o in ops]  # noqa
    ops = {"/device:TPU:0": _step(0) + _step(400 * US),
           "/device:TPU:1": hidden + shift(hidden, 400 * US)}
    mods = {d: [("jit_serve_step(3)", 0, 300 * US),
                ("jit_serve_step(3)", 400 * US, 700 * US),
                ("jit__pages_into_pool(4)", 800 * US, 900 * US),
                ("jit_serve_step(3)", 950 * US, 1200 * US)]
            for d in ops}
    assert _reader().read(_ctx(ops, mods)) == pytest.approx(0.050 / 2)


def test_nothing_to_read():
    """No combine op in any execution, or no program trace: no value."""
    ops = {"/device:TPU:0": [("%fusion.1 = bf16[8] fusion()", 0, 100 * US,
                              ATTN)]}
    mods = {"/device:TPU:0": [("jit_serve_step(3)", 0, 300 * US)]}
    assert _reader().read(_ctx(ops, mods)) is None
    assert _reader().read(types.SimpleNamespace(
        trace=None, program_trace=None)) is None


def test_four_chip_cell_resolves():
    cell = harness.load_cell(tiny.ROOT, "granite-8b-kvp4.long_decode")
    assert cell.chips == 4 and cell.config["mesh"] == [4, 1]
    assert [m["name"] for m in cell.end_to_end] == ["ttl_p50_ms",
                                                    "ttl_p95_ms", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == [
        "serve_step_device_ms", "device_idle_share", "host_step_ms",
        "kv_pool_device_ms", "unscoped_device_ms", "combine_exposed_ms"]
    assert cell.config["attention_multiplier"] == 128 ** -0.5
    cfg = harness.arch_config(cell.config)
    assert (cfg.n_layers, cfg.d_model, cfg.hsz, cfg.d_ff, cfg.padded_vocab) \
        == (40, 4096, 128, 12800, 49664)
    assert cell.limits is not None
