"""Operation and byte counts against hand-computed ones, for the cell's
configuration and for granite-8b's shapes (head 128, untied head)."""
import json
import pathlib

import pytest

BENCH = pathlib.Path(__file__).resolve().parent.parent


GRANITE_8B = {"num_hidden_layers": 36, "hidden_size": 4096,
              "num_attention_heads": 32, "num_key_value_heads": 8,
              "head_dim": 128, "intermediate_size": 14336,
              "vocab_size": 49152, "tie_word_embeddings": False,
              "torch_dtype": "bfloat16"}


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def costs():
    import harness
    return harness.load_module(BENCH / "costs" / "dense_gqa.py")


def test_param_counts(costs):
    # granite-3-2b: 40 x (2048*(32+16)*64 + 2048*2048 + 3*2048*8192 + 2*2048)
    #   + 2048 + 49155*2048 (tied)
    layer = 2048 * 48 * 64 + 2048 * 2048 + 3 * 2048 * 8192 + 2 * 2048
    assert costs.params(_cfg("granite-3-2b")) == 40 * layer + 2048 + 49155 * 2048
    # granite-8b: untied head, hsz 128
    layer = 4096 * 48 * 128 + 4096 * 4096 + 3 * 4096 * 14336 + 2 * 4096
    assert costs.params(GRANITE_8B) == \
        36 * layer + 4096 + 49152 * 4096


def test_flash_decode_call(costs):
    c = _cfg("granite-3-2b")
    flops, nbytes = costs.flash_decode(c, [6000, 5000, 0, 100])
    n = 11100
    assert flops == 4 * n * 32 * 64
    assert nbytes == 2 * n * 8 * 64 * 2 + 2 * 3 * 32 * 64 * 2
    flops, nbytes = costs.flash_decode(GRANITE_8B, [24000])
    assert flops == 4 * 24000 * 32 * 128
    assert nbytes == 2 * 24000 * 8 * 128 * 2 + 2 * 32 * 128 * 2


def test_decode_step_and_least_time(costs):
    c = _cfg("granite-3-2b")
    p = costs.params(c)
    flops, nbytes = costs.decode_step(c, [6144, 5632, 5120, 4608])
    fa, ba = costs.flash_decode(c, [6144, 5632, 5120, 4608])
    assert flops == 2 * p * 4 + 40 * fa
    assert nbytes == 2 * p + 40 * ba
    peak = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = costs.least_seconds(flops, nbytes, peak)
    assert bound == "bandwidth" and t == pytest.approx(nbytes / 819e9)
    t4, _ = costs.least_seconds(flops, nbytes, peak, chips=4)
    assert t4 == pytest.approx(t / 4)
