"""A broken timed path must make ``correct`` false: each fault a serving
cell can have, planted under a full run of the harness (the check for a
chip skipped), and the float8 control in the program's place."""
import time

import jax

import harness
import tiny


def _run(cell, seed=11):
    return harness.run(cell, seed, 2.0, False, time.perf_counter(),
                       jax.devices()[:cell.chips], log=lambda m: None)


def _limited(name, **kw):
    cell = tiny.tiny_cell(name, **kw)
    cell.limits = harness.load_cell(tiny.ROOT, name).limits
    return cell


def _wrap_serve_step(monkeypatch, wrap):
    import repro.models.model_zoo as zoo
    orig = zoo.build_serve_step

    def build(*a, **kw):
        step = orig(*a, **kw)
        return lambda params, state, tokens: wrap(step, params, state,
                                                  tokens)
    monkeypatch.setattr(zoo, "build_serve_step", build)


def test_sound_run_is_correct():
    assert _run(_limited("granite-3-2b.long_decode"))["correct"] is True


def test_step_returning_its_state_unchanged(monkeypatch):
    def wrap(step, params, state, tokens):
        nxt, _ = step(params, state, tokens)
        return nxt, state
    _wrap_serve_step(monkeypatch, wrap)
    assert _run(_limited("granite-3-2b.long_decode"))["correct"] is False


def test_half_of_the_batch_left_out(monkeypatch):
    def wrap(step, params, state, tokens):
        nxt, new = step(params, state, tokens)
        half = nxt.shape[0] // 2
        return nxt.at[half:].set(tokens[half:]), new
    _wrap_serve_step(monkeypatch, wrap)
    assert _run(_limited("granite-3-2b.long_decode"))["correct"] is False


def test_token_altered_where_produced(monkeypatch):
    def wrap(step, params, state, tokens):
        nxt, new = step(params, state, tokens)
        return (nxt + 1) % tiny.TINY["vocab_size"], new
    _wrap_serve_step(monkeypatch, wrap)
    assert _run(_limited("granite-3-2b.long_decode"))["correct"] is False


def test_float8_control_fails_the_limit():
    cell = _limited("granite-3-2b.long_decode")
    keep = {}
    out = harness.run(cell, 5, 2.0, False, time.perf_counter(),
                      jax.devices()[:cell.chips], log=lambda m: None,
                      keep=keep)
    assert out["correct"] is True
    gap, n = harness.served_gap(cell, keep["params"], keep["sample"],
                                quant="fp8")
    assert n > 0
    check = harness.compared(cell, gap, n)
    assert not harness.is_correct(check), check
