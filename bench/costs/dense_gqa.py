"""Operations and bytes of the served work of a dense GQA decoder.

Worked out from shapes alone, as the least the algorithm needs: every
weight and every live K/V row read once, at the configuration's dtype,
with causal attention touching only the keys a query may see.  Counts
are for the whole call, summed over the chips that share it; divide by
the chip count for one chip's share.  ``c`` is a configuration file's
dict (Hugging Face key names).
"""
from __future__ import annotations

import numpy as np

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(c: dict) -> dict:
    d, qh = c["hidden_size"], c["num_attention_heads"]
    hsz = c.get("head_dim") or d // qh
    return dict(layers=c["num_hidden_layers"], d=d, qh=qh,
                kh=c["num_key_value_heads"], hsz=hsz,
                f=c["intermediate_size"], vocab=c["vocab_size"],
                tied=bool(c.get("tie_word_embeddings")),
                wb=DTYPE_BYTES[c["torch_dtype"]])


def layer_params(c: dict) -> int:
    m = dims(c)
    attn = m["d"] * (m["qh"] + 2 * m["kh"]) * m["hsz"] + m["qh"] * m["hsz"] * m["d"]
    return attn + 3 * m["d"] * m["f"] + 2 * m["d"]


def params(c: dict) -> int:
    """Parameters read by one step: every layer, the final norm, and the
    logits head (the tied embedding counts once)."""
    m = dims(c)
    return m["layers"] * layer_params(c) + m["d"] + m["vocab"] * m["d"]


def flash_decode(c: dict, lengths) -> tuple[float, float]:
    """One decode-attention call (one layer): each row's query heads
    against its ``lengths[b]`` cached positions, K and V read once."""
    m = dims(c)
    n = float(np.sum(np.asarray(lengths, np.float64)))
    rows = int(np.count_nonzero(np.asarray(lengths)))
    flops = 4.0 * n * m["qh"] * m["hsz"]
    kv = 2.0 * n * m["kh"] * m["hsz"] * m["wb"]
    qo = 2.0 * rows * m["qh"] * m["hsz"] * m["wb"]
    return flops, kv + qo


def decode_step(c: dict, lengths) -> tuple[float, float]:
    """One whole decode step: one token for each live row."""
    m = dims(c)
    rows = int(np.count_nonzero(np.asarray(lengths)))
    fa, ba = flash_decode(c, lengths)
    flops = 2.0 * params(c) * rows + m["layers"] * fa
    return flops, params(c) * m["wb"] + m["layers"] * ba


def least_seconds(flops: float, nbytes: float, peak: dict,
                  chips: int = 1) -> tuple[float, str]:
    """The least time ``chips`` chips could take, and which peak bounds
    it."""
    tf = flops / (peak["flops_bf16"] * chips)
    tb = nbytes / (peak["hbm_bytes_per_s"] * chips)
    return (tf, "compute") if tf >= tb else (tb, "bandwidth")
