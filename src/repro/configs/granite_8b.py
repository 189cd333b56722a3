"""granite-8b [dense] — llama-arch code model, arXiv:2405.04324.

36L d_model=4096 32H (GQA kv=8) d_ff=14336 vocab=49152: the code model's
widths, which the CPU tests' ``reduced()`` variants start from.  The
benchmark runs granite-3.1-8b-base instead (40L, d_ff 12800, vocab
49155, 131,072 positions at rope_theta 1e7): its config file
``bench/configs/granite-8b-kvp4.json`` names this entry as ``arch`` and
sets every width (``bench/harness.py`` ``arch_config``).
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="granite-8b",
    family="dense",
    n_layers=36,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=14_336,
    vocab=49_152,
)
