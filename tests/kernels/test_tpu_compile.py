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

The granite-3.1-8b tests compile the programs of the four-chip KVP=4
cell at its published widths, its pool and its longest prompt: the
chunked-prefill step, the pool handoff and the serve step, each within a
chip's memory, and the serve step's exchange under the ``combine`` scope.

The serve-step test compiles a 2-layer paged decode step and reads its
HLO: the ``op_name`` metadata carries the step's named scopes
(runtime/trace.py), which the benchmark's trace reduction attributes
device time by, and the decode kernel's instruction stays
``flash_decode``, the op kind its roofline reads.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.core.kvcache import (decode_state_shapes, decode_state_specs,
                                page_positions)
from repro.core.sharding import (HelixConfig, MeshPolicy, helix_param_specs,
                                 to_shardings, train_roles)
from repro.kernels.flash_decode.ops import flash_decode
from repro.kernels.flash_prefill.ops import flash_prefill
from repro.kernels.w8a16_matmul.ops import w8a16_matmul
from repro.models.attention import prefill_attention
from repro.models.decode_model import build_serve_step
from repro.models.model_zoo import (make_chunk_prefill_step,
                                    prefill_buffer_spec, prefill_to_pool)
from repro.models.transformer import init_params
from repro.runtime.trace import SCOPES

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


@pytest.mark.parametrize("case", ["bf16-served", "int8", "kvp4-4row",
                                  "kvp4-served", "grouped"])
def test_flash_decode_paged_blocks_compile(one_chip, case):
    """The paged kernel's S-blocks of whole pages at served shapes: one
    HOP-B row of bf16 16-row pages against a 1,728-page table, 512-slot
    blocks (32 pages a step), fused append; an int8 pool with its scales;
    one KVP=4 rank's 4-row pages, against a 40-page table (the block capped
    at the table) and against the 1,728-page table (128 pages a block: 256
    page-slot operands, each double-buffered); and grouped shared-prefix
    decode, whose prefix pass is its own kernel (``flash_decode_prefix``).
    """
    b, rows, pages, dt, kvp, rank = {
        "bf16-served": (1, PAGE, 1728, jnp.bfloat16, 1, 0),
        "int8": (B, PAGE, MAX_PAGES, jnp.int8, 1, 0),
        "kvp4-4row": (B, PAGE // 4, MAX_PAGES, jnp.bfloat16, 4, 2),
        "kvp4-served": (1, PAGE // 4, 1728, jnp.bfloat16, 4, 2),
        "grouped": (B, PAGE, MAX_PAGES, jnp.bfloat16, 1, 0),
    }[case]
    quant = dt == jnp.int8
    n_pool = b * pages + 1
    new_dt = jnp.float32 if quant else dt

    def step(q, k, v, tl, tables, kn, vn, *scales):
        kw = dict(kscale=scales[0], vscale=scales[1]) if quant else {}
        if case == "grouped":
            kw["groups"] = (jnp.zeros_like(tl), tl // rows)
        return flash_decode(q, k, v, tl, rank, kvp=kvp, rr_block=rows,
                            block_s=512, k_new=kn, v_new=vn, prune=True,
                            block_tables=tables, interpret=False, **kw)

    shapes = [((b, QH, HSZ), jnp.bfloat16),
              ((n_pool, KH, rows, HSZ), dt), ((n_pool, KH, rows, HSZ), dt),
              ((b,), jnp.int32), ((b, pages), jnp.int32),
              ((b, KH, HSZ), new_dt), ((b, KH, HSZ), new_dt)]
    if quant:
        shapes += [((n_pool, KH, rows), jnp.float32)] * 2
    hlo = _compile(step, one_chip, *shapes).as_text()
    assert _kernel_kinds(hlo) == ({"flash_decode", "flash_decode_prefix"}
                                  if case == "grouped" else {"flash_decode"})


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


# ------------------------------------------------- scopes and kernel names
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\(")
_NO_WORK = ("get-tuple-element", "bitcast", "parameter", "tuple", "while")


def _instructions(hlo: str):
    """(name, result shape, opcode, op_name, line) of every instruction."""
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            on = re.search(r'op_name="([^"]*)"', line)
            yield (m.group(1), m.group(2), m.group(3),
                   on.group(1) if on else "", line)


def _scope(op_name: str) -> str:
    """The innermost program scope named in ``op_name``."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return "(unscoped)"


def _kernel_kinds(hlo: str) -> set[str]:
    return {re.sub(r"\.\d+$", "", name)
            for name, _, _, _, line in _instructions(hlo)
            if 'custom_call_target="tpu_custom_call"' in line}


def test_serve_step_scopes_and_kernel_name(topo):
    """A 2-layer paged serve step at granite-3-2b widths, Pallas decode:
    its ops carry the step's scopes, every op outside the kernel that
    yields a pool plane is pool plumbing or unattributed, and the kernel
    keeps the op kind ``flash_decode``."""
    cfg = dataclasses.replace(get_config("granite-3-2b"), n_layers=2)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1),
                ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    hx = HelixConfig(kvp_axes=("data",), paged_kv=True,
                     attn_backend="pallas", prefill_backend="pallas")
    rep = NamedSharding(mesh, P())
    shaped = lambda t: jax.tree.map(                      # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), t)
    params = jax.eval_shape(lambda k: init_params(cfg, k, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    state = dict(decode_state_shapes(cfg, B, MAX_PAGES * PAGE, 1, PAGE,
                                     jnp.bfloat16, pool_blocks=N_POOL,
                                     max_pages=MAX_PAGES))
    state["total_len"] = jax.ShapeDtypeStruct((B,), jnp.int32)
    tokens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=rep)
    hlo = jax.jit(build_serve_step(cfg, mesh, hx)).lower(
        shaped(params), shaped(state), tokens).compile().as_text()

    instrs = list(_instructions(hlo))
    paths = {part for *_, on, _ in instrs for part in on.split("/")}
    assert {"embed", "attn", "qkv", "kv_pool", "ffn", "lm_head",
            "sample"} <= paths
    plane = f"{N_POOL},{KH},{PAGE},{HSZ}]"
    pool_ops = [(name, op, on) for name, shape, op, on, line in instrs
                if plane in shape and op not in _NO_WORK
                and "tpu_custom_call" not in line]
    assert pool_ops
    for name, op, on in pool_ops:
        where = _scope(on)
        if where == "(unscoped)" and ("state['kcache']" in on
                                      or "state['vcache']" in on):
            where = "kv_pool"
        assert where in ("kv_pool", "(unscoped)"), (name, op, on)
    assert _kernel_kinds(hlo) == {"flash_decode"}


def test_kernels_are_named():
    """Every Pallas kernel names itself; grouped shared-prefix decode runs
    two, and the prefix pass is ``flash_decode_prefix`` while the suffix
    pass stays ``flash_decode`` (traced in interpret mode; the compiled
    pair is ``test_flash_decode_paged_blocks_compile[grouped]``)."""
    q = jnp.zeros((B, QH, HSZ), jnp.float32)
    pool = jnp.zeros((N_POOL, KH, PAGE, HSZ), jnp.float32)
    rows = jnp.zeros((B,), jnp.int32)
    tables = jnp.zeros((B, MAX_PAGES), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: flash_decode(*a, 0, kvp=1, rr_block=PAGE,
                                block_tables=tables, groups=(rows, rows),
                                interpret=True))(q, pool, pool, rows)

    def names(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from names(sub)

    assert sorted(names(jaxpr.jaxpr)) == ["flash_decode",
                                          "flash_decode_prefix"]


# ------------------------------------- granite-3.1-8b, KVP=4 on four chips
# bench/configs/granite-8b-kvp4.json and bench/traffic/kvp4_long_decode.json
G8 = dict(n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8, head_dim=128,
          d_ff=12_800, vocab=49_155, tie_embeddings=True, rope_theta=1e7)
G8_PROMPT, G8_CHUNK, G8_POOL, G8_NEW = 12_288, 512, 46_080, 1536
CHIP_BYTES = 16e9


@pytest.fixture(scope="module")
def granite8b_kvp4(four_chips):
    """(cfg, hx, placed params, placed decode state, buffer shapes) of the
    four-chip cell, as shapes with the engine's shardings."""
    mesh = four_chips
    cfg = dataclasses.replace(get_config("granite-8b"), **G8)
    hx = HelixConfig(kvp_axes=("data",), paged_kv=True,
                     attn_backend="pallas", prefill_backend="pallas")
    params = jax.eval_shape(lambda k: init_params(cfg, k, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    psh = to_shardings(mesh, helix_param_specs(cfg, params, hx, mesh))
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        params, psh)
    n_pool = -(-G8_POOL // page_positions(4, hx.rr_block)) + 1
    specs = decode_state_specs(cfg, hx, B, mesh)
    specs["total_len"] = P(None)
    st_sh = to_shardings(mesh, specs)
    shapes = decode_state_shapes(cfg, B, G8_PROMPT + G8_NEW + 1, 4,
                                 hx.rr_block, jnp.bfloat16,
                                 pool_blocks=n_pool, max_pages=n_pool - 1)
    shapes["total_len"] = jax.ShapeDtypeStruct((B,), jnp.int32)
    state = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=st_sh[k])
             for k, v in shapes.items()}
    buf = jax.ShapeDtypeStruct(
        (40, 1, G8_PROMPT, 8, 128), jnp.bfloat16,
        sharding=NamedSharding(mesh, prefill_buffer_spec(cfg, mesh)))
    return cfg, hx, params, state, {"kcache": buf, "vcache": buf}


def _fits_a_chip(name, compiled):
    m = compiled.memory_analysis()
    per_chip = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes)
    print(f"[granite-8b kvp4] {name}: arguments "
          f"{m.argument_size_in_bytes:,} B, outputs "
          f"{m.output_size_in_bytes:,} B, temporaries "
          f"{m.temp_size_in_bytes:,} B a chip")
    assert per_chip < CHIP_BYTES, (name, per_chip)
    return m


def test_granite8b_kvp4_chunk_step_compiles(four_chips, granite8b_kvp4):
    """A 512-token chunk of the 12,288-token prompt: the carry buffers in
    bf16, kv heads split over the chips, stay so in the output."""
    cfg, hx, params, _, bufs = granite8b_kvp4
    rep = NamedSharding(four_chips, P())
    c = jax.jit(make_chunk_prefill_step(cfg, four_chips, hx)).lower(
        params, jax.ShapeDtypeStruct((1, G8_CHUNK), jnp.int32, sharding=rep),
        bufs, jax.ShapeDtypeStruct((1,), jnp.int32, sharding=rep)).compile()
    m = _fits_a_chip("chunk step", c)
    quarter = 2 * 40 * G8_PROMPT * 8 * 128 * 2 // 4     # K and V, bf16
    assert m.output_size_in_bytes < 1.01 * quarter
    assert c.output_shardings[1]["kcache"].is_equivalent_to(
        bufs["kcache"].sharding, 5)


def test_granite8b_kvp4_pool_handoff_compiles(four_chips, granite8b_kvp4):
    """The handoff of the finished 12,288-token prefill into the pool:
    the kv-head split becomes the pool's sequence split by all-to-alls,
    with no all-gather of the buffers."""
    import functools
    cfg, hx, _, state, bufs = granite8b_kvp4
    planes = {k: state[k] for k in ("kcache", "vcache")}
    sh = {k: v.sharding for k, v in planes.items()}
    fn = jax.jit(functools.partial(prefill_to_pool, cfg, hx, kvp=4),
                 static_argnames=("t", "s0"), donate_argnums=(0,),
                 out_shardings=sh)
    n = -(-G8_PROMPT // page_positions(4, hx.rr_block))
    c = fn.lower(planes, bufs, jax.ShapeDtypeStruct(
        (n,), jnp.int32, sharding=NamedSharding(four_chips, P())),
        t=G8_PROMPT, s0=0).compile()
    m = _fits_a_chip("pool handoff", c)
    pool = sum(v.size * v.dtype.itemsize for v in planes.values()) // 4
    assert m.alias_size_in_bytes == pool                   # donated
    ops = {op for _, _, op, _, _ in _instructions(c.as_text())}
    assert "all-to-all" in ops and not {"all-gather", "all-gather-start"} & ops


def test_granite8b_kvp4_serve_step_compiles(four_chips, granite8b_kvp4):
    """The decode step of 4 rows over the 46,080-token pool, and every
    op of the exchange (all-to-all, all-gather, async or not) under the
    ``combine`` scope."""
    cfg, hx, params, state, _ = granite8b_kvp4
    rep = NamedSharding(four_chips, P())
    st_sh = {k: v.sharding for k, v in state.items()}
    c = jax.jit(build_serve_step(cfg, four_chips, hx),
                out_shardings=(rep, st_sh)).lower(
        params, state, jax.ShapeDtypeStruct((B,), jnp.int32,
                                            sharding=rep)).compile()
    _fits_a_chip("serve step", c)
    exchange = [(name, op, on) for name, _, op, on, _ in
                _instructions(c.as_text())
                if re.match(r"all-(to-all|gather)", op) and "/attn/" in on]
    assert "all-to-all" in {op for _, op, _ in exchange}
    for name, op, on in exchange:
        assert _scope(on) == "combine", (name, op, on)
