"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode proves a kernel's semantics on the CPU; it cannot show what
Mosaic refuses on the chip (block shapes off the (8, 128) tiling, boolean
selects, lane-splitting reshapes, VMEM over-use).  These tests compile each
kernel the served decode path runs, at granite-3-2b widths (32 q / 8 kv
heads, hsz 64, vocab 49,664), with ``interpret=False`` against a
``v5e:2x2`` topology described in-process: nothing runs, but the TPU
compiler accepts or refuses exactly as it would on the chip.  One test
compiles the prefill attention of the sharded forward on the four chips.

The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU compiler library.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core.sharding import MeshPolicy, train_roles
from repro.kernels.flash_decode.ops import flash_decode
from repro.kernels.flash_prefill.ops import flash_prefill
from repro.kernels.w8a16_matmul.ops import w8a16_matmul
from repro.models.attention import prefill_attention

# granite-3-2b decode widths (configs/granite_3_2b.py)
QH, KH, HSZ, D_MODEL, VOCAB = 32, 8, 64, 2048, 49_664
B = 4                      # engine max_batch
PAGE = 16                  # paged pool page rows (HelixConfig.rr_block)
MAX_PAGES = 40             # 640 positions per request
N_POOL = B * MAX_PAGES + 1  # + the reserved sink page 0


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The host's 4 chips as the serving mesh: (4, 1) ("data", "model")."""
    devs = np.array(topo.devices[:4]).reshape(4, 1)
    return Mesh(devs, ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Mosaic kernel
    assert compiled.memory_analysis() is not None
    return compiled


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_paged_fused_append_compiles(one_chip, dtype):
    """The serve step's attention: paged pool, block pruning, and the
    fused KV-append epilogue writing through aliased page windows."""
    def step(q, k, v, tl, tables, kn, vn):
        return flash_decode(q, k, v, tl, 0, kvp=1, rr_block=PAGE,
                            k_new=kn, v_new=vn, prune=True,
                            block_tables=tables, interpret=False)

    _compile(step, one_chip,
             ((B, QH, HSZ), dtype),
             ((N_POOL, KH, PAGE, HSZ), dtype),
             ((N_POOL, KH, PAGE, HSZ), dtype),
             ((B,), jnp.int32), ((B, MAX_PAGES), jnp.int32),
             ((B, KH, HSZ), dtype), ((B, KH, HSZ), dtype))


def test_flash_decode_kvp4_rank_compiles(one_chip):
    """One KVP=4 rank's local attend (a quarter page per rank)."""
    def step(q, k, v, tl, tables, kn, vn):
        return flash_decode(q, k, v, tl, 2, kvp=4, rr_block=PAGE,
                            k_new=kn, v_new=vn, prune=True,
                            block_tables=tables, interpret=False)

    _compile(step, one_chip,
             ((B, QH, HSZ), jnp.float32),
             ((N_POOL, KH, PAGE, HSZ), jnp.float32),
             ((N_POOL, KH, PAGE, HSZ), jnp.float32),
             ((B,), jnp.int32), ((B, MAX_PAGES), jnp.int32),
             ((B, KH, HSZ), jnp.float32), ((B, KH, HSZ), jnp.float32))


def test_flash_prefill_chunked_ragged_compiles(one_chip):
    """A packed prefill chunk: per-row q_offset and valid lengths over the
    carry buffers, causal block skipping."""
    c, t = 256, 512

    def chunk(q, k, v, offs, lens):
        return flash_prefill(q, k, v, causal=True, q_offset=offs,
                             seq_lens=lens, prune=True, interpret=False)

    _compile(chunk, one_chip,
             ((B, c, QH, HSZ), jnp.float32),
             ((B, t, KH, HSZ), jnp.float32),
             ((B, t, KH, HSZ), jnp.float32),
             ((B,), jnp.int32), ((B,), jnp.int32))


def test_flash_prefill_paged_compiles(one_chip):
    """Prefill attention streaming pool pages through a block table."""
    c = 256

    def chunk(q, k, v, offs, lens, tables):
        return flash_prefill(q, k, v, causal=True, q_offset=offs,
                             seq_lens=lens, block_tables=tables, prune=True,
                             interpret=False)

    _compile(chunk, one_chip,
             ((B, c, QH, HSZ), jnp.float32),
             ((N_POOL, KH, PAGE, HSZ), jnp.float32),
             ((N_POOL, KH, PAGE, HSZ), jnp.float32),
             ((B,), jnp.int32), ((B,), jnp.int32),
             ((B, MAX_PAGES), jnp.int32))


@pytest.mark.parametrize("batch", [B, 3])
def test_flash_prefill_on_mesh_compiles(four_chips, batch):
    """A chunk's attention in the sharded forward (``MeshPolicy``), its
    operands split over the batch by GSPMD: the kernel must run per shard,
    since GSPMD cannot partition a Mosaic kernel.  3 rows do not divide
    the 4 data shards and are replicated instead."""
    c, t = 128, 512
    policy = MeshPolicy(four_chips, train_roles(four_chips))
    rows = NamedSharding(four_chips, P("data") if batch % 4 == 0 else P())

    def chunk(q, k, v, offs, lens):
        return prefill_attention(q, k, v, causal=True, q_offset=offs,
                                 seq_lens=lens, backend="pallas",
                                 policy=policy)

    _compile(chunk, rows,
             ((batch, c, QH, HSZ), jnp.float32),
             ((batch, t, KH, HSZ), jnp.float32),
             ((batch, t, KH, HSZ), jnp.float32),
             ((batch,), jnp.int32), ((batch,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_w8a16_lm_head_compiles(one_chip, dtype):
    """The int8-weight lm_head matmul of a decode step."""
    def head(x, qw, scale):
        return w8a16_matmul(x, qw, scale, interpret=False)

    _compile(head, one_chip,
             ((B, D_MODEL), dtype), ((D_MODEL, VOCAB), jnp.int8),
             ((VOCAB,), jnp.float32))
