"""The engine's profiler spans (runtime/trace.py) and its pool counter.

Under ``jax.profiler`` every ``DecodeEngine.step`` writes a ``serve.step``
span holding one span per phase; a decode phase holds its dispatch, the
blocking token read-back and the replay, in the single-step and the
windowed path alike.  Without a profiler session the spans record
nothing.
"""
import pathlib

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core.sharding import HelixConfig
from repro.models.model_zoo import (build_serve_multistep, build_serve_step,
                                    make_chunk_prefill_step,
                                    make_prefill_step)
from repro.models.transformer import init_params
from repro.runtime.trace import SPANS
from repro.serving import DecodeEngine, Request
from repro.utils import make_mesh, set_mesh

CFG = get_config("granite-3-2b").reduced()
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
MESH = make_mesh((1, 1), ("data", "model"))
HX = HelixConfig(kvp_axes=(), tpa_axis=None, attn_block_s=16, paged_kv=True)


def _engine(window):
    with set_mesh(MESH):
        return DecodeEngine(
            CFG, PARAMS, build_serve_step(CFG, MESH, HX),
            make_prefill_step(CFG, MESH, HX), max_batch=2, max_seq=64,
            hx=HX, chunk_tokens=8,
            chunk_prefill_step=make_chunk_prefill_step(CFG, MESH, HX),
            tp_width=1, decode_window=window,
            serve_multistep=(build_serve_multistep(CFG, MESH, HX,
                                                   window=window)
                             if window > 1 else None))


def _submit(eng, max_new=6):
    rng = np.random.default_rng(3)
    for i in range(2):
        eng.submit(Request(rid=i, prompt=rng.integers(0, CFG.vocab,
                                                      12).tolist(),
                           max_new_tokens=max_new))


def _spans(logdir: pathlib.Path):
    """(name, start, end, stats) of every ``serve.*`` host span."""
    xp = sorted(logdir.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(xp)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("serve.")]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(spans, outer, name):
    return [s for s in spans if s[0] == name
            and outer[1] <= s[1] and s[2] <= outer[2]]


@pytest.mark.parametrize("window", [1, 3])
def test_each_step_nests_its_phases(tmp_path, window):
    eng = _engine(window)
    _submit(eng)
    with set_mesh(MESH):
        eng.step()                          # compile outside the trace
        jax.profiler.start_trace(str(tmp_path))
        steps = 0
        while eng.pending():
            eng.step()
            steps += 1
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    assert {s[0] for s in spans} <= set(SPANS)
    outer = [s for s in spans if s[0] == "serve.step"]
    assert len(outer) == steps
    assert [s[3]["step_num"] for s in outer] == list(range(2, steps + 2))
    decoded = 0
    for st in outer:
        for name in ("serve.admit", "serve.decode", "serve.govern"):
            assert len(_inside(spans, st, name)) == 1, name
        dec = _inside(spans, st, "serve.decode")[0]
        kids = [_inside(spans, dec, n) for n in
                ("serve.dispatch", "serve.readback", "serve.replay")]
        if any(kids):
            decoded += 1
            (d,), (r,), (p,) = kids
            assert d[2] <= r[1] and r[2] <= p[1]
    assert decoded >= 2


def test_prefill_spans_carry_rid_and_tokens(tmp_path):
    eng = _engine(1)
    with set_mesh(MESH):
        jax.profiler.start_trace(str(tmp_path))
        _submit(eng)
        for _ in range(3):
            eng.step()
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    pre = [s for s in spans if s[0] == "serve.prefill"]
    # two 12-token prompts in chunks of 8: 2 x 8, then 2 x 4 tokens
    assert [(s[3]["rid"], s[3]["tokens"]) for s in pre] == [(0, 16), (0, 8)]
    assert len(_inside(spans, pre[1], "serve.readback")) == 1
    assert not _inside(spans, pre[0], "serve.readback")


def test_pool_frag_mean_is_the_mean_of_its_samples():
    """The pool counter keeps a running sum and count: the mean over
    decode steps of 1 - committed positions / allocated page slots."""
    eng = _engine(1)
    _submit(eng, max_new=9)
    samples = []
    with set_mesh(MESH):
        while eng.pending():
            syncs = eng.decode_syncs
            eng.step()
            if eng.decode_syncs > syncs and eng.pool.used_count:
                samples.append(1.0 - sum(eng.sched.slot_len)
                               / (eng.pool.used_count * eng.block_s))
    assert len(samples) >= 6
    assert eng.pool_stats()["pool_frag_mean"] == pytest.approx(
        float(np.mean(samples)), rel=1e-12)
