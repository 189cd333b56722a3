"""One benchmark run of one cell: build, warm up, drive, check, report.

Everything that belongs to one configuration, traffic mix or metric is
found by name: ``BENCHMARK.json`` names the cell; its configuration file
(``bench/configs``) names the architecture, whose reference and weight
generator live in ``bench/reference/<architecture>.py`` and whose cost
arithmetic lives in ``bench/costs/<architecture>.py``; the mix is
``bench/traffic/<traffic>.json``; each metric is read by
``bench/metrics/<metric>.py``; the limits of the comparison that decides
``correct`` are ``bench/limits/<cell>.json``.

The served model is built through the program's public path and driven
by ``DecodeEngine.submit``/``step`` alone.  The host clock times the
window; with tracing on, ``jax.profiler`` records the device and the
harness's own host spans (``SPANS``).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import sys
import tempfile
import time

import numpy as np

import loadgen

BENCH = pathlib.Path(__file__).resolve().parent
SPANS = ("engine.step",)
FINISHED_OK = ("eos", "max_tokens")


# ------------------------------------------------------------ the cell
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    limits: dict | None
    bench: pathlib.Path = BENCH


def _load_json(path: pathlib.Path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def _applies(metric: dict, cell: str, reported: set[str] | None = None
             ) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric.get("moves") in reported


def run_config(c: dict) -> dict:
    """Configuration file ``c`` as the program runs it: the published
    values, with those the program departs from (``departures``) in
    their place."""
    return {**c, **c.get("departures", {}).get("runs_as", {})}


def load_cell(root: pathlib.Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its files."""
    spec = _load_json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench = root / "bench"
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    names = {m["name"] for m in e2e}
    per = [m for m in spec["per_layer"] if _applies(m, name, names)]
    lim = bench / "limits" / f"{name}.json"
    config = run_config(_load_json(root / entry["file"]))
    mix = loadgen.load_mix(w["traffic"], bench / "traffic")
    if loadgen.max_context(mix) > config["max_position_embeddings"]:
        raise SystemExit(f"{name}: contexts pass the configuration's "
                         f"{config['max_position_embeddings']} positions")
    return Cell(name, w["chips"], config, mix, e2e, per,
                _load_json(lim) if lim.exists() else None, bench)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------- the device
def device_facts(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def peak_for(kind: str, bench: pathlib.Path = BENCH) -> dict:
    peaks = _load_json(bench / "peaks.json")
    if kind not in peaks:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def memory_peak(devices) -> int | None:
    vals = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            vals.append(int(stats["peak_bytes_in_use"]))
    return max(vals) if vals else None


def use_compile_cache(root: pathlib.Path) -> None:
    """JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache/``, every program cached however fast it compiled."""
    import os
    import jax
    cache = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Compiles:
    """Backend compiles and persistent-cache hits, from JAX's own
    monitoring events; ``mark`` snapshots the counts."""

    def __init__(self):
        import jax
        self.n = 0
        self.secs = 0.0
        self.hits = 0
        self.names: dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += duration
            name = kw.get("fun_name", "?")
            self.names[name] = self.names.get(name, 0) + 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self) -> tuple[int, float, int]:
        return self.n, self.secs, self.hits

    def since(self, names: dict) -> dict:
        return {k: v - names.get(k, 0) for k, v in self.names.items()
                if v > names.get(k, 0)}


# ------------------------------------------------------ building it
def arch_config(c: dict):
    """The program's ArchConfig for configuration file ``c``."""
    from repro.configs import get_config
    base = get_config(c["arch"])
    plain = {"hidden_act": "silu", "rms_norm_eps": 1e-6,
             "attention_multiplier": c["head_dim"] ** -0.5,
             "embedding_multiplier": 1.0, "residual_multiplier": 1.0,
             "logits_scaling": 1.0}
    off = {k: c.get(k) for k, v in plain.items()
           if c.get(k, v) != v and not (isinstance(v, float)
                                        and math.isclose(c[k], v))}
    if off:
        raise SystemExit(f"{c['name']}: the program cannot run {off or 'act'}")
    return dataclasses.replace(
        base, n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        tie_embeddings=bool(c["tie_word_embeddings"]),
        rope_theta=float(c["rope_theta"]))


def build(cell: Cell, seed: int, devices, max_ctx: int):
    """(engine, weights): the cell's model served through the program's
    public path, with weights made by the benchmark from ``seed``."""
    import jax
    import jax.numpy as jnp
    from repro.core.kvcache import page_positions
    from repro.core.sharding import (HelixConfig, helix_param_specs,
                                     to_shardings)
    from repro.models.model_zoo import (build_serve_step,
                                        make_chunk_prefill_step,
                                        make_prefill_step)
    from repro.models.transformer import init_params
    from repro.serving import DecodeEngine
    from repro.utils import make_mesh

    c, mix = cell.config, cell.mix
    cfg = arch_config(c)
    dtype = jnp.dtype(c["torch_dtype"])
    n = math.prod(c["mesh"])
    mesh = make_mesh(tuple(c["mesh"]), ("data", "model"),
                     devices=np.asarray(devices[:n]))
    h = dict(c["helix"])
    h["kvp_axes"] = tuple(h["kvp_axes"])
    hx = HelixConfig(**h)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k, dtype),
                            jax.random.PRNGKey(0))
    ref = load_module(cell.bench / "reference" / f"{c['architecture']}.py")
    params = ref.init_weights(
        c, seed, shapes, dtype,
        to_shardings(mesh, helix_param_specs(cfg, shapes, hx, mesh)))
    kvp = hx.kvp(mesh)
    block_s = page_positions(kvp, hx.rr_block)
    engine = DecodeEngine(
        cfg, params, build_serve_step(cfg, mesh, hx),
        make_prefill_step(cfg, mesh, hx), max_batch=mix["max_batch"],
        max_seq=max_ctx + 1, kvp=kvp, hx=hx, dtype=dtype,
        chunk_tokens=c["chunk_tokens"],
        chunk_prefill_step=make_chunk_prefill_step(cfg, mesh, hx),
        tp_width=mesh.shape["model"],
        pool_blocks=-(-mix["pool_tokens"] // block_s) + 1, mesh=mesh)
    return engine, params


# ------------------------------------------------------ the host record
@dataclasses.dataclass
class ReqRecord:
    item: loadgen.Item
    req: object
    last_t: float | None = None
    seen: int = 0


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    decode_lengths: list[int]            # cached positions each row reads
    traced: bool


class Recorder:
    """Deliveries to the host and the work each engine step did."""

    def __init__(self, engine, clock=time.perf_counter):
        self.engine = engine
        self.clock = clock
        self.reqs: dict[int, ReqRecord] = {}
        self.open: dict[int, ReqRecord] = {}
        self.steps: list[StepRecord] = []
        # (host time, rid, tokens delivered, each later token's gap)
        self.deliveries: list[tuple[float, int, int, list[float]]] = []
        self.recording = False
        self.tracing = False

    def submit(self, item: loadgen.Item) -> None:
        from repro.serving import Request
        req = Request(rid=item.rid, prompt=list(item.prompt),
                      max_new_tokens=item.max_new)
        rec = ReqRecord(item, req)
        self.reqs[item.rid] = self.open[item.rid] = rec
        self.engine.submit(req)

    def step(self) -> None:
        from repro.serving.scheduler import DECODE
        before = [(r, r.state, len(r.out_tokens))
                  for r in self.engine.slots if r is not None]
        t0 = self.clock()
        self.engine.step()
        t1 = self.clock()
        for rec in list(self.open.values()):
            n = len(rec.req.out_tokens)
            if n > rec.seen:
                k = n - rec.seen
                if rec.seen == 0:
                    gaps = [0.0] * (k - 1)
                else:
                    gaps = [(t1 - rec.last_t) / k] * k
                self.deliveries.append((t1, rec.item.rid, k, gaps))
                rec.last_t, rec.seen = t1, n
            if rec.req.done:
                del self.open[rec.item.rid]
        if self.recording:
            dec = [len(r.prompt) + n for r, st, n in before if st == DECODE]
            self.steps.append(StepRecord(t0, t1, dec, self.tracing))


# --------------------------------------------------------- the window
class Tracer:
    """The profiler over the last ``seconds`` of the window."""

    def __init__(self, on: bool, seconds: float | None):
        self.on, self.seconds = on, seconds
        self.dir = None
        self.active = False

    def maybe_start(self, now: float, end: float, rec: Recorder) -> None:
        if (self.on and not self.active and self.dir is None
                and (self.seconds is None or now >= end - self.seconds)):
            import jax
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # spans only, not every call
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.active = rec.tracing = True

    def stop(self, rec: Recorder) -> None:
        if self.active:
            import jax
            jax.profiler.stop_trace()
            self.active = rec.tracing = False


def _span(name: str, on: bool):
    if on:
        import jax
        return jax.profiler.TraceAnnotation(name)
    import contextlib
    return contextlib.nullcontext()


def drive(rec: Recorder, t_open: float, seconds: float,
          tracer: Tracer | None = None) -> float:
    """Step the engine until ``seconds`` have passed; returns the host
    time the window closed (the end of the step that crossed the
    deadline)."""
    clock = rec.clock
    end = t_open + seconds
    now = clock()
    while now < end:
        if tracer is not None:
            tracer.maybe_start(now, end, rec)
        if rec.engine.pending():
            with _span("engine.step", tracer is not None and tracer.active):
                rec.step()
        else:
            time.sleep(end - now)
        now = clock()
    return now


def settle(rec: Recorder, until) -> None:
    """Step the engine until ``until()`` holds (set-up only)."""
    guard = 0
    while not until():
        rec.step()
        guard += 1
        if guard > 100_000:
            raise RuntimeError("set-up did not settle")


# ------------------------------------------------------------ checking
def served_gap(cell: Cell, params, sample: list[ReqRecord],
               quant: str | None = None) -> tuple[float, int]:
    """Widest gap, in reference-row standard deviations, by which a
    served token's reference logit lies below the reference's best; and
    the number of tokens compared.  With ``quant``, the tokens compared
    are the ones the lower-precision reference puts first."""
    ref = load_module(cell.bench / "reference"
                      / f"{cell.config['architecture']}.py")
    worst, count = 0.0, 0
    for r in sample:
        p, out = list(r.req.prompt), list(r.req.out_tokens)
        seq = p + out[:-1]
        rows = np.arange(len(p) - 1, len(seq))
        logits = ref.logits_at(cell.config, params, seq, rows)
        toks = out
        if quant is not None:
            toks = np.argmax(ref.logits_at(cell.config, params, seq, rows,
                                           quant=quant), -1)
        g = ref.served_gaps(logits, toks)
        worst, count = max(worst, float(g.max())), count + len(g)
    return worst, count


def compared(cell: Cell, gap: float, count: int) -> dict:
    lim = (cell.limits or {}).get("max_gap", {}).get("limit")
    want = cell.mix["check_tokens"]
    return {"max_gap": {"value": gap, "limit": lim},
            "tokens_compared": {"value": count, "limit": want}}


def is_correct(check: dict) -> bool:
    g, n = check["max_gap"], check["tokens_compared"]
    return (g["limit"] is not None and g["value"] <= g["limit"]
            and n["value"] >= n["limit"])


# ------------------------------------------------------------- the run
@dataclasses.dataclass
class Context:
    """What a metric reader reads."""
    cell: Cell
    costs: object
    peak: dict
    chips: int
    setup_s: float
    t_open: float
    t_close: float
    steps: list[StepRecord]
    deliveries: list
    trace: object | None = None


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices, log=lambda m: print(m, file=sys.stderr, flush=True),
        keep=None) -> dict:
    """One run; returns the result line as a dict.  ``keep`` (a dict), if
    given, receives the weights and the compared sessions for a caller
    that reads more from the same run (calibration)."""
    import jax
    compiles = Compiles()
    dev = device_facts(devices)
    peak = peak_for(dev["kind"], cell.bench) if dev["platform"] == "tpu" \
        else None
    c, mix = cell.config, cell.mix
    items = loadgen.generate(mix, seed, c["vocab_size"])
    engine, params = build(cell, seed, devices, loadgen.max_context(mix))
    rec = Recorder(engine)
    for it in items:
        rec.submit(it)
        settle(rec, lambda: rec.reqs[it.rid].seen > 0)
    for _ in range(mix["warm_steps"]):
        rec.step()
    jax.effects_barrier()
    n0, s0, h0 = compiles.mark()
    names0 = dict(compiles.names)
    t_open = rec.clock()
    setup_s = t_open - t_start
    log(f"[bench] set-up {setup_s:.2f} s; compiles so far {n0} "
        f"({s0:.1f} s), cache hits {h0}")
    tracer = Tracer(trace, mix.get("trace_s"))
    rec.recording = True
    t_close = drive(rec, t_open, seconds, tracer)
    rec.recording = False
    tracer.stop(rec)
    n1, s1, h1 = compiles.mark()
    log(f"[bench] window {t_close - t_open:.3f} s, {len(rec.steps)} steps; "
        f"compiles inside it {n1 - n0} ({s1 - s0:.2f} s)"
        + (f": {compiles.since(names0)}" if n1 > n0 else ""))
    sessions = [rec.reqs[it.rid] for it in items]
    failed = sum(1 for r in sessions
                 if r.req.done and r.req.finish_reason not in FINISHED_OK)
    costs = load_module(cell.bench / "costs" / f"{c['architecture']}.py")
    ctx = Context(cell, costs, peak, cell.chips, setup_s, t_open, t_close,
                  rec.steps,
                  [d for d in rec.deliveries if t_open < d[0] <= t_close])
    if trace and tracer.dir is not None:
        import devtrace
        ctx.trace = devtrace.load(devtrace.find_xplane(tracer.dir), SPANS)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        if m["name"] == "setup_s":
            v = setup_s
        else:
            v = load_module(cell.bench / "metrics"
                            / f"{m['name']}.py").read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(dev, memory_peak_bytes=None)
    out = {"attempted": len(sessions), "failed": failed, "metrics": metrics,
           "device": device}
    if trace and ctx.trace is not None:
        import devtrace
        lo, hi = ctx.trace.window()
        devs = ctx.trace.devices
        device["busy_s"] = (sum(devtrace.busy_ns(ctx.trace, d, lo, hi)
                                for d in devs) / max(len(devs), 1) / 1e9)
        device["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = devtrace.breakdown(ctx.trace)
        import shutil
        shutil.rmtree(tracer.dir, ignore_errors=True)

    # the comparison: every session's served tokens against the
    # reference, run with the program's state freed
    device["memory_peak_bytes"] = memory_peak(devices)
    sample = [r for r in sessions if r.req.out_tokens]
    if keep is not None:
        keep.update(params=params, sample=sample)
    del engine, rec.engine
    gc.collect()
    t_c = time.perf_counter()
    gap, count = served_gap(cell, params, sample)
    check = compared(cell, gap, count)
    log(f"[bench] reference over {len(sample)} requests, {count} tokens, "
        f"{time.perf_counter() - t_c:.1f} s")
    out = {"correct": is_correct(check), **out,
           "check": check}
    return out
