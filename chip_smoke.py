#!/usr/bin/env python3
"""Bring-up smoke test: the served decode path at full width on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # a four-chip host: KVP=4 Helix decode

One chip: granite-3-2b at its published widths (40 layers, d_model 2048,
32 q / 8 kv heads, vocab 49,155; random weights from a seed) serves four
requests (prompts of 256-512 tokens, 32 new tokens each) through the normal
path — ``serve_demo`` -> ``DecodeEngine`` -> chunked prefill and the paged
KV pool — with no backend flags, so the platform picks compiled Pallas for
``flash_decode`` and ``flash_prefill``.  Every generated token is then
checked against a plain reference: one teacher-forced forward over prompt +
generated tokens on the ``ref`` (pure jnp) backends.

Four chips (``--four-chips``): the same requests on a (4, 1) ("data",
"model") mesh with KVP=4 over a sequence-sharded paged pool, parameters
and decode state placed by their sharding specs, compared with the same
requests served on one device of that host; both streams pass the
reference check.

Earlier lines print set-up facts (backends, compile seconds, tokens, wall
seconds, peak device bytes) — not a benchmark.  The last line is one JSON
object naming the device; it is printed only when every phase passed.  The
script exits non-zero, before any work, where JAX finds no TPU, and runs
everything in this one process (a chip belongs to one process at a time).
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time
from collections import defaultdict

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "granite-3-2b"
PROMPT_LENS = (512, 384, 256, 384)
MAX_NEW = 32
CHUNK_TOKENS = 128
SEED = 0
# A served token may differ from the reference argmax only where the two
# are a near tie: the reference logit of the served token is within
# TIE_TOL * (std of that logit row) of the reference maximum.  The served
# path and the reference sum in different orders (the Pallas kernels'
# blockwise online softmax, the KVP combine, vs jnp's one-shot softmax);
# in float32 those orders differ by ~1e-6 relative per op, ~1e-5 after 40
# layers, far below 1e-3 of the spread of 49k logits — while a wrong
# kernel moves logits by a large fraction of it, at almost every token.
TIE_TOL = 1e-3


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileTimes:
    """Backend compile seconds per jitted function, from JAX's own
    monitoring events (persistent-cache hits are counted separately)."""

    def __init__(self):
        import jax
        self.secs = defaultdict(float)
        self.count = defaultdict(int)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            name = kw.get("fun_name", "?")
            self.secs[name] += duration
            self.count[name] += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def report(self) -> dict:
        return {k: (self.count[k], self.secs[k]) for k in self.secs}


def print_compiles(times: dict) -> None:
    main = tuple(f"jit({n})" for n in ("serve_step", "chunk_step",
                                         "prefill_step", "init_params",
                                         "reference"))
    rest_n = rest_s = 0
    for name, (n, s) in sorted(times.items(), key=lambda kv: -kv[1][1]):
        if name in main:
            log(f"compile {name}: {n} program(s), {s:.1f} s")
        else:
            rest_n, rest_s = rest_n + n, rest_s + s
    log(f"compile other (eager ops, small jits): {rest_n} program(s), "
        f"{rest_s:.1f} s")


def trace_rows():
    from repro.serving.workload import TraceRow
    return [TraceRow(rid=i, arrival_step=0, prompt_len=p, max_tokens=MAX_NEW,
                     seed=SEED * 1000 + i)
            for i, p in enumerate(PROMPT_LENS)]


def serve(mesh=None, hx=None):
    """One serve_demo run of the smoke workload; returns (finished
    requests sorted by rid, wall seconds)."""
    from repro.launch.serve import serve_demo
    t0 = time.perf_counter()
    finished, _ = serve_demo(
        ARCH, reduced=False, n_requests=len(PROMPT_LENS),
        prompt_len=max(PROMPT_LENS), max_new=MAX_NEW,
        max_batch=len(PROMPT_LENS), mesh=mesh, hx=hx, paged_kv=True,
        chunk_tokens=CHUNK_TOKENS, trace=trace_rows(), seed=SEED,
        log=log)
    wall = time.perf_counter() - t0
    return sorted(finished, key=lambda r: r.rid), wall


def check_served(finished) -> None:
    """Every request finished on its token budget."""
    assert len(finished) == len(PROMPT_LENS), \
        f"{len(finished)} of {len(PROMPT_LENS)} requests finished"
    for r in finished:
        assert r.finish_reason == "max_tokens", (r.rid, r.finish_reason)
        assert len(r.out_tokens) == MAX_NEW, (r.rid, len(r.out_tokens))
        assert len(r.prompt) == PROMPT_LENS[r.rid], (r.rid, len(r.prompt))


def reference_check(streams: dict[str, list]) -> None:
    """Teacher-forced reference forward over prompt + generated tokens on
    the ``ref`` backends (one device, float32 matmuls at full precision);
    each served token must be the reference argmax at its position, or a
    near tie (see TIE_TOL).  ``streams`` maps a label to its finished
    requests; all are checked against the same reference weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch.serve import init_serving_params
    from repro.models.layers import full_precision
    from repro.models.transformer import forward

    cfg = get_config(ARCH)
    params = init_serving_params(cfg, SEED)

    @jax.jit
    @full_precision
    def reference(params, tokens):
        logits, _ = forward(cfg, params, tokens, prefill_backend="ref",
                            ssd_backend="ref")
        return logits[..., :cfg.vocab]

    for label, finished in streams.items():
        seqs = [list(r.prompt) + list(r.out_tokens[:-1]) for r in finished]
        width = max(len(s) for s in seqs)
        toks = np.zeros((len(seqs), width), np.int32)   # right pad: causal
        for i, s in enumerate(seqs):
            toks[i, :len(s)] = s
        logits = np.asarray(reference(params, jnp.asarray(toks)))
        exact = ties = 0
        worst = 0.0
        for i, r in enumerate(finished):
            p = len(r.prompt)
            rows = logits[i, p - 1:p - 1 + len(r.out_tokens)]
            for j, tok in enumerate(r.out_tokens):
                row = rows[j]
                best = int(np.argmax(row))
                if best == tok:
                    exact += 1
                    continue
                gap = float(row[best] - row[tok])
                tol = TIE_TOL * float(np.std(row))
                worst = max(worst, gap / max(tol, 1e-30))
                assert gap <= tol, (
                    f"{label}: request {r.rid} token {j}: served {tok}, "
                    f"reference argmax {best}, logit gap {gap:.3e} > "
                    f"tolerance {tol:.3e}")
                ties += 1
        log(f"reference check [{label}]: {exact} tokens == argmax, {ties} "
            f"near ties within tolerance (worst {worst:.2f} of it), "
            f"0 mismatches")
    del params
    gc.collect()


def peak_bytes(devices) -> list:
    out = []
    for d in devices:
        stats = d.memory_stats()
        out.append(None if stats is None
                   else stats.get("peak_bytes_in_use"))
    return out


def one_chip(compiles: CompileTimes) -> None:
    import jax
    from repro.configs import get_config

    cfg = get_config(ARCH)
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} q / {cfg.n_kv_heads} kv heads, hsz {cfg.hsz}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab} (padded {cfg.padded_vocab}), "
        f"reduced=False")
    log(f"workload: prompts {list(PROMPT_LENS)}, {MAX_NEW} new tokens "
        f"each, max_batch {len(PROMPT_LENS)}, chunk_tokens {CHUNK_TOKENS}, "
        f"paged KV")
    finished, wall = serve()
    check_served(finished)
    toks = sum(len(r.out_tokens) for r in finished)
    log(f"served: {len(finished)} requests finished, {toks} tokens, "
        f"{wall:.1f} s wall (compiles included)")
    log(f"peak_bytes_in_use: {peak_bytes(jax.devices()[:1])}")
    gc.collect()
    reference_check({"one chip": finished})
    print_compiles(compiles.report())
    log(f"persistent compile-cache hits: {compiles.cache_hits}")


def four_chips(compiles: CompileTimes) -> None:
    import jax
    from repro.core.sharding import HelixConfig
    from repro.utils import make_mesh

    devs = jax.devices()
    assert len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}"
    mesh = make_mesh((4, 1), ("data", "model"))
    hx = HelixConfig(kvp_axes=("data",), tpa_axis=None, paged_kv=True)
    log(f"sharded run: mesh {dict(mesh.shape)}, KVP={hx.kvp(mesh)}, "
        f"backends {hx.attn_backend}/{hx.prefill_backend}")
    sharded, wall4 = serve(mesh=mesh, hx=hx)
    check_served(sharded)
    # params and pool placed by their specs: no device holds the whole
    # 10 GB model (a placement onto device 0 would show here)
    peaks = peak_bytes(devs)
    log(f"sharded: {wall4:.1f} s wall, per-device peak_bytes_in_use {peaks}")
    gc.collect()
    log("one-device run of the same requests (device 0)")
    single, wall1 = serve()
    check_served(single)
    log(f"one device: {wall1:.1f} s wall")
    same = sum(a == b for r1, r4 in zip(single, sharded)
               for a, b in zip(r1.out_tokens, r4.out_tokens))
    total = sum(len(r.out_tokens) for r in single)
    log(f"KVP=4 vs one device: {same} of {total} tokens identical")
    gc.collect()
    reference_check({"KVP=4": sharded, "one device": single})
    print_compiles(compiles.report())
    known = [p for p in peaks if p is not None]
    assert not known or max(known) < 9e9, \
        f"a device held most of the 10 GB model: peaks {peaks}"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the KVP=4 sharded-decode phase and its "
                         "one-device comparison (needs a four-chip host)")
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        print("[smoke] FAIL: no TPU found; this smoke test runs on the chip "
              "only", file=sys.stderr)
        return 1

    sys.path.insert(0, str(ROOT / "src"))
    from repro.utils import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    from repro.core.sharding import HelixConfig
    probe = HelixConfig(kvp_axes=())
    log(f"default backends: flash_decode={probe.attn_backend} "
        f"flash_prefill={probe.prefill_backend}")
    assert probe.attn_backend == probe.prefill_backend == "pallas", probe

    compiles = CompileTimes()
    if args.four_chips:
        four_chips(compiles)
    else:
        one_chip(compiles)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
