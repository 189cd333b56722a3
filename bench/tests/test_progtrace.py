"""The program's spans and scopes in a trace: attribution of device time
to scopes, the host's share of a step, the clock offset and the naming
of idle gaps, on hand-made traces; and the recorded chip trace, which
has neither, read as before."""
import pathlib
import types

import pytest

import devtrace
import harness
import progtrace
import tiny

RECORDED = pathlib.Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"
SCOPES = progtrace.program_names()[1]
US = 1000


def _step(t0):
    """Host spans of an engine step starting at ``t0`` (us), 400 us
    long: admit 20, decode 360 (dispatch 40, readback 240, replay 80),
    govern 10 and 10 us before it."""
    u = lambda a, b: ((t0 + a) * US, (t0 + b) * US)         # noqa: E731
    return [("serve.step", *u(0, 400)), ("serve.admit", *u(0, 20)),
            ("serve.decode", *u(20, 380)), ("serve.dispatch", *u(20, 60)),
            ("serve.readback", *u(60, 300)), ("serve.replay", *u(300, 380)),
            ("serve.govern", *u(390, 400))]


def _ops(x0):
    """Device ops of one serve-step execution starting at ``x0`` (ns on
    the device clock): a loop holding the layer's ops, and two ops
    outside it."""
    body = x0 + 10 * US
    return [
        ("%while.1 = (...) while()", body, body + 200 * US,
         "jit(serve_step)/while"),
        ("%fusion.3 = bf16[4] fusion()", body, body + 20 * US,
         "jit(serve_step)/while/body/closed_call/attn/qkv/dot_general"),
        ("%dynamic-slice_bitcast_fusion.1 = bf16[9] fusion()",
         body + 20 * US, body + 50 * US,
         "jit(serve_step)/while/body/closed_call/kv_pool/squeeze"),
        ("%copy.8 = bf16[9] copy()", body + 50 * US, body + 90 * US, ""),
        ("%flash_decode.1 = (bf16[9]) custom-call()", body + 90 * US,
         body + 180 * US,
         "jit(serve_step)/while/body/closed_call/attn/"
         "jit(flash_decode)/pallas_call"),
        ("%constant_dynamic-update-slice_fusion.1 = bf16[9] fusion()",
         body + 180 * US, body + 200 * US,
         "jit(serve_step)/while/body/dynamic_update_slice"),
        ("%copy.83 = bf16[9] copy()", x0, x0 + 10 * US, "state['kcache']"),
        ("%fusion.9 = s32[4] fusion()", body + 200 * US, body + 210 * US,
         "jit(serve_step)/sample/argmax"),
    ]


def _hand_made(offset_us=0):
    """Two engine steps of 400 us at host 0 and 500 us; each step's
    program runs 220 us, from 30 us after its dispatch began to 30 us
    before its readback ended (device clock = host clock +
    ``offset_us``)."""
    d = offset_us * US
    spans = _step(0) + _step(500)
    ops, mods = [], []
    for t0 in (0, 500):
        x0 = (t0 + 50) * US + d
        ops += _ops(x0)
        mods.append(("jit_serve_step(7)", x0, x0 + 220 * US))
    trace = devtrace.Trace({DEV: [o[:3] for o in ops]}, {DEV: mods},
                           [("engine.step", 0, 450 * US),
                            ("engine.step", 500 * US, 950 * US)])
    pt = progtrace.ProgramTrace({DEV: ops}, spans, SCOPES,
                                ("jit_serve_step(7)",))
    return trace, pt


def test_scope_of():
    assert SCOPES == ("embed", "attn", "qkv", "kv_pool", "combine", "ffn",
                      "lm_head", "sample")
    f = lambda n: progtrace.scope_of(n, SCOPES)              # noqa: E731
    assert f("jit(serve_step)/while/body/closed_call/attn/qkv/dot") == "qkv"
    assert f("jit(s)/attn/jit(flash_decode)/kv_pool/reshape") == "kv_pool"
    assert f("jit(s)/attn/jit(flash_decode)/pallas_call") == "attn"
    assert f("state['kcache']") == "kv_pool"
    assert f("state[\\'vcache\\']") == "kv_pool"          # as HLO text has it
    assert f("jit(serve_step)/while/body/dynamic_update_slice") == \
        progtrace.UNSCOPED
    assert f("") == progtrace.UNSCOPED
    # a program that declares no scopes attributes nothing to them
    assert progtrace.scope_of("jit(s)/attn/dot", ()) == progtrace.UNSCOPED


def test_ops_take_the_op_name_of_the_program_around_them():
    """The same instruction name in two programs: each op takes the
    op_name its own program's HLO gives it; an op outside every program
    execution, or an instruction the HLO lacks, gets none."""
    ops = [("%copy.8 = bf16[9] copy()", 10, 20),
           ("%copy.8 = bf16[9] copy()", 110, 120),
           ("%fusion.1 = f32[2] fusion()", 130, 140),
           ("%copy.8 = bf16[9] copy()", 210, 220)]
    mods = [("jit_a(1)", 0, 100), ("jit_b(2)", 100, 200)]
    hlo = {"jit_a(1)": {"copy.8": "jit(a)/kv_pool/copy"},
           "jit_b(2)": {"copy.8": "state['kcache']"}}
    assert [o[3] for o in progtrace.name_ops(ops, mods, hlo)] == [
        "jit(a)/kv_pool/copy", "state['kcache']", "", ""]


def test_op_names_from_a_profile(tmp_path):
    """The compiled HLO the profile keeps for a program gives each
    instruction the named scopes it was traced under."""
    import jax
    import jax.numpy as jnp
    import xspace

    def step(x):
        with jax.named_scope("kv_pool"):
            y = x * 2
        with jax.named_scope("attn"):
            return (y @ x).sum()

    f = jax.jit(step)
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    xp = devtrace.find_xplane(tmp_path)
    (prog,) = [p for p in xspace.hlo_protos(xp.read_bytes())
               if p.startswith("jit_step(")]
    names = xspace.op_names(xp, [prog])[prog]
    scopes = {progtrace.scope_of(n, SCOPES) for n in names.values()}
    assert {"kv_pool", "attn"} <= scopes
    assert any(n.endswith("attn/dot_general") for n in names.values())
    # another id of the one program of that name reads the same HLO
    assert xspace.op_names(xp, ["jit_step(0)"]) == {"jit_step(0)": names}
    assert xspace.op_names(xp, ["jit_other(1)"]) == {}


def test_scopes_per_execution_close_the_account():
    trace, pt = _hand_made()
    ms = progtrace.scope_ms(pt, trace)
    assert ms == pytest.approx({"qkv": 0.020, "kv_pool": 0.030 + 0.010,
                                progtrace.UNSCOPED: 0.040 + 0.020,
                                "attn": 0.090, "sample": 0.010})
    whole = 0.220                           # each execution's device time
    assert sum(ms.values()) == pytest.approx(whole)


def test_host_step_ms_leaves_out_the_readback():
    trace, pt = _hand_made()
    lo, hi = trace.window()
    assert progtrace.host_step_ms(pt, lo, hi) == pytest.approx(0.400 - 0.240)
    assert progtrace.host_step_ms(pt, 0, 300 * US) == pytest.approx(0.160)
    assert progtrace.host_step_ms(pt, 0, 100 * US) is None


@pytest.mark.parametrize("offset_us", [0, -20, 120])
def test_clock_offset_and_gap_names(offset_us):
    """The program starts 30 us after its dispatch span starts and ends
    30 us before its readback span ends (host clock), so the device
    clock's offset lies in [offset - 30, offset + 30] us; spans move onto
    the device's clock only once that interval is 50 us clear of 0."""
    trace, pt = _hand_made(offset_us)
    window = trace.window()
    progtrace.attach(trace, pt)
    assert trace.window() == window == (0, 950 * US)
    assert pt.offset_ns == ((offset_us - 30) * US, (offset_us + 30) * US)
    assert pt.shift_ns == (offset_us * US if offset_us == 120 else 0)
    # each program span, at its host time plus the shift, names the time
    at = lambda t: devtrace.span_at(trace, t * US + pt.shift_ns)  # noqa
    assert [at(10), at(40), at(200), at(350), at(395), at(420)] == [
        "serve.admit", "serve.dispatch", "serve.readback", "serve.replay",
        "serve.govern", "engine.step"]


def test_idle_gaps_named_by_program_spans():
    """Device idle before the program (the host dispatching it), after it
    (the host replaying its tokens) and between steps (the harness's own
    bookkeeping, outside ``serve.step``)."""
    trace, pt = _hand_made()
    assert {n for n, _ in devtrace.breakdown(trace)["idle_gaps"]} == {
        "engine.step"}
    progtrace.attach(trace, pt)
    gaps = devtrace.breakdown(trace)["idle_gaps"]
    assert sorted((n, round(v * 1e6)) for n, v in gaps) == [
        ("engine.step", 280), ("serve.dispatch", 50), ("serve.replay", 180)]


def test_of_run_on_a_cpu_trace(tmp_path, monkeypatch):
    """A CPU profile has no device plane: nothing is read.  ``locate``
    finds the profile the harness's spans came from, and ``load`` reads
    the program's spans from it."""
    import jax
    import jax.numpy as jnp
    from repro.runtime.trace import span, step_span
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    (tmp_path / "bench_trace_old").mkdir()
    d = tmp_path / "bench_trace_new"
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation("engine.step"):
        with step_span(1), span("serve.decode"):
            with span("serve.dispatch"):
                y = f(x)
            with span("serve.readback"):
                y.block_until_ready()
    jax.profiler.stop_trace()
    xp = devtrace.find_xplane(d)
    trace = devtrace.load(xp, ("engine.step",))
    assert progtrace.locate(trace) == xp
    pt = progtrace.load(xp, *progtrace.program_names())
    assert [s[0] for s in pt.spans] == ["serve.step", "serve.decode",
                                       "serve.dispatch", "serve.readback"]
    assert pt.ops == {} and pt.programs == ()
    ctx = types.SimpleNamespace(trace=trace)
    assert progtrace.of_run(ctx) is None and ctx.program_trace is None


@pytest.mark.skipif(not (RECORDED / "long_decode_trace.json.gz").exists(),
                    reason="no recorded chip trace")
def test_recorded_trace_reads_as_before():
    """The recorded chip step (op names, modules and the harness's spans,
    no program spans) loads, and the four readers of PR 12's per-layer
    metrics give the values they gave before program spans existed,
    with and without spans attached."""
    t = devtrace.load_json(RECORDED / "long_decode_trace.json.gz")
    cell = harness.load_cell(tiny.ROOT, "granite-3-2b.long_decode")
    costs = harness.load_module(cell.bench / "costs" / "dense_gqa.py")
    step = harness.StepRecord(0.0, 0.8146, [6200, 5700, 5200, 4700], True)
    ctx = harness.Context(cell, costs, harness.peak_for("TPU v5 lite"), 1,
                          0.0, 0.0, 0.8146, [step], [], t)
    want = {"serve_step_device_ms": 812.170779,
            "flash_decode_roofline": 0.29475946278057397,
            "step_mfu": 1.027378249270712,
            "device_idle_share": 0.00012629587982093682}

    def readings():
        return {m: harness.load_module(cell.bench / "metrics"
                                       / f"{m}.py").read(ctx) for m in want}

    assert readings() == pytest.approx(want, rel=1e-12)
    window = t.window()
    lo, hi = window
    mid = (lo + hi) // 2
    pt = progtrace.ProgramTrace({}, [("serve.step", lo + 10, hi - 10),
                                     ("serve.decode", lo + 20, mid)],
                                SCOPES)
    progtrace.attach(t, pt)
    assert t.window() == window
    assert readings() == pytest.approx(want, rel=1e-12)
    ctx.program_trace = pt
    for m in ("kv_pool_device_ms", "unscoped_device_ms"):
        assert harness.load_module(cell.bench / "metrics"
                                   / f"{m}.py").read(ctx) == 0.0


@pytest.mark.skipif(
    not (RECORDED / "long_decode_program_trace.json.gz").exists(),
    reason="no recorded chip trace")
def test_recorded_program_trace():
    """One engine step of the long-decode cell recorded on a v5e with the
    program's spans, each op carrying the op_name its instruction has in
    the profile's HLO: the scopes close the step program's account, and
    the readings are those the chip run printed for this step."""
    import gzip
    import json
    with gzip.open(RECORDED / "long_decode_program_trace.json.gz", "rt") as f:
        d = json.load(f)
    tup = lambda evs: [tuple(e) for e in evs]                # noqa: E731
    trace = devtrace.Trace({k: [tuple(o[:3]) for o in v]
                            for k, v in d["ops"].items()},
                           {k: tup(v) for k, v in d["modules"].items()},
                           tup(d["engine_spans"]))
    pt = progtrace.ProgramTrace({k: tup(v) for k, v in d["ops"].items()},
                                tup(d["spans"]), SCOPES,
                                tuple(d["programs"]))
    (_, s, e), = trace.modules[DEV]
    ms = progtrace.scope_ms(pt, trace)
    assert ms["kv_pool"] == pytest.approx(23.790599)
    assert ms[progtrace.UNSCOPED] == pytest.approx(38.447135)
    assert ms["attn"] == pytest.approx(741.3151)
    assert sum(ms.values()) == pytest.approx((e - s) / 1e6, rel=1e-4)
    window = trace.window()
    assert progtrace.host_step_ms(pt, *window) == pytest.approx(3.451159)
    progtrace.attach(trace, pt)
    assert trace.window() == window
    assert pt.offset_ns == (-2522406, -417441)
    assert pt.shift_ns == -1469924
    gaps = devtrace.breakdown(trace)["idle_gaps"]
    assert gaps[0][0] == "serve.replay"
    assert all(n.startswith("serve.") for n, _ in gaps)
