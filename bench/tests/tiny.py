"""A cell of the benchmark cut to a size the CPU runs in seconds, driven
through the same harness functions as ``bench/run.py``."""
from __future__ import annotations

import dataclasses
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

TINY = {"num_hidden_layers": 2, "hidden_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 256,
        "vocab_size": 512, "attention_multiplier": 32 ** -0.5,
        "chunk_tokens": 32}


def tiny_cell(name: str, root: pathlib.Path = ROOT, backend: str = "ref",
              cell: harness.Cell | None = None, **mix) -> harness.Cell:
    cell = cell or harness.load_cell(root, name)
    cfg = dict(cell.config, **TINY)
    cfg["helix"] = dict(cfg["helix"], attn_backend=backend,
                        prefill_backend=backend)
    m = dict(cell.mix, contexts=[96, 80, 64, 48], max_new_tokens=400,
             pool_tokens=4 * 512, trace_s=None)
    m.update(mix)
    return dataclasses.replace(cell, config=cfg, mix=m,
                               limits={"max_gap": {"limit": 0.05}})
