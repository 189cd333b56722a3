import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core.sharding import HelixConfig
from repro.core.helix import helix_attention, append_kv, rr_slot_of_position, prefill_to_rr_layout
from repro.kernels.flash_decode.ref import flash_decode_ref, shard_positions
from repro.utils import make_mesh, set_mesh

mesh = make_mesh((4, 2), ("data", "model"))

# ---- pure-KVP mode: KVP=8 over both axes ----
hx = HelixConfig(kvp_axes=("data", "model"), tpa_axis=None)
B, QH, KH, HSZ, KVP, RR = 4, 8, 2, 64, 8, 16
S_CAP = KVP * 32  # 32 local slots per rank
total_len = 200
rng = np.random.default_rng(0)
q = jnp.asarray(rng.standard_normal((B, QH, HSZ), np.float32))

# build global contiguous KV then convert to rr layout
kg = jnp.asarray(rng.standard_normal((B, KH, S_CAP, HSZ), np.float32))
vg = jnp.asarray(rng.standard_normal((B, KH, S_CAP, HSZ), np.float32))
k_rr = prefill_to_rr_layout(kg, KVP, RR)
v_rr = prefill_to_rr_layout(vg, KVP, RR)

with set_mesh(mesh):
    out = jax.jit(lambda q, k, v: helix_attention(mesh, hx, q, k, v, total_len))(q, k_rr, v_rr)
ref, _ = flash_decode_ref(q, kg[:, :, :total_len], vg[:, :, :total_len], total_len, 0, kvp=1)
ref_flat = ref.reshape(B, QH * HSZ)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref_flat), rtol=2e-5, atol=2e-5)
print("pure-KVP helix == unsharded ref: OK")

# ---- HOP-B chunked gives identical results ----
with set_mesh(mesh):
    out2 = jax.jit(lambda q, k, v: helix_attention(mesh, hx, q, k, v, total_len, hopb_chunks=2))(q, k_rr, v_rr)
np.testing.assert_allclose(np.asarray(out2), np.asarray(ref_flat), rtol=2e-5, atol=2e-5)
print("HOP-B chunked == ref: OK")

# ---- 2-D mode: KVP=4 (data), TPA=2 (model) ----
hx2 = HelixConfig(kvp_axes=("data",), tpa_axis="model")
with set_mesh(mesh):
    k_rr2 = prefill_to_rr_layout(kg, 4, RR)
    v_rr2 = prefill_to_rr_layout(vg, 4, RR)
    out3 = jax.jit(lambda q, k, v: helix_attention(mesh, hx2, q, k, v, total_len))(q, k_rr2, v_rr2)
np.testing.assert_allclose(np.asarray(out3), np.asarray(ref_flat), rtol=2e-5, atol=2e-5)
print("2-D (KVP x TPA) helix == ref: OK")

# ---- per-request lengths ----
tls = jnp.asarray([200, 37, 150, 9], jnp.int32)
with set_mesh(mesh):
    out4 = jax.jit(lambda q, k, v: helix_attention(mesh, hx, q, k, v, tls))(q, k_rr, v_rr)
for i, tl in enumerate([200, 37, 150, 9]):
    r, _ = flash_decode_ref(q[i:i+1], kg[i:i+1, :, :tl], vg[i:i+1, :, :tl], tl, 0, kvp=1)
    np.testing.assert_allclose(np.asarray(out4[i]), np.asarray(r.reshape(QH*HSZ)), rtol=2e-5, atol=2e-5)
print("per-request total_len: OK")

# ---- pallas-interpret backend == ref through the all-to-all + combine ----
import dataclasses
hx_pl = dataclasses.replace(hx, attn_backend="pallas-interpret")
hx2_pl = dataclasses.replace(hx2, attn_backend="pallas-interpret")
with set_mesh(mesh):
    pl1 = jax.jit(lambda q, k, v: helix_attention(mesh, hx_pl, q, k, v,
                                                  total_len))(q, k_rr, v_rr)
    pl2 = jax.jit(lambda q, k, v: helix_attention(mesh, hx_pl, q, k, v,
                                                  tls))(q, k_rr, v_rr)
    pl3 = jax.jit(lambda q, k, v: helix_attention(mesh, hx2_pl, q, k, v,
                                                  total_len))(q, k_rr2, v_rr2)
    pl4 = jax.jit(lambda q, k, v: helix_attention(mesh, hx_pl, q, k, v,
                                                  total_len, window=64))(
                                                      q, k_rr, v_rr)
    rf4 = jax.jit(lambda q, k, v: helix_attention(mesh, hx, q, k, v,
                                                  total_len, window=64))(
                                                      q, k_rr, v_rr)
np.testing.assert_allclose(np.asarray(pl1), np.asarray(out), rtol=2e-6,
                           atol=2e-6)
np.testing.assert_allclose(np.asarray(pl2), np.asarray(out4), rtol=2e-6,
                           atol=2e-6)
np.testing.assert_allclose(np.asarray(pl3), np.asarray(out3), rtol=2e-6,
                           atol=2e-6)
np.testing.assert_allclose(np.asarray(pl4), np.asarray(rf4), rtol=2e-6,
                           atol=2e-6)
print("pallas-interpret backend == ref (scalar, [B] tl, 2-D, windowed): OK")

# ---- block pruning == dense masked sweep through the 8-way shard_map ----
hx_nopr = dataclasses.replace(hx_pl, prune_blocks=False)
with set_mesh(mesh):
    for tl_case, win in ((total_len, 0), (total_len, 64), (tls, 64)):
        pr = jax.jit(lambda q, k, v: helix_attention(
            mesh, hx_pl, q, k, v, tl_case, window=win))(q, k_rr, v_rr)
        de = jax.jit(lambda q, k, v: helix_attention(
            mesh, hx_nopr, q, k, v, tl_case, window=win))(q, k_rr, v_rr)
        np.testing.assert_array_equal(np.asarray(pr), np.asarray(de))
print("block pruning == dense (KVP=8, scalar + [B] tl, windowed): OK")

# ---- fused KV-append epilogue == unfused through the 8-way shard_map ----
kn = jnp.asarray(rng.standard_normal((B, KH, HSZ), np.float32))
vn = jnp.asarray(rng.standard_normal((B, KH, HSZ), np.float32))
for tl_new in (total_len + 1, jnp.asarray([201, 38, 151, 10], jnp.int32)):
    kc_u, vc_u = append_kv(k_rr, v_rr, kn, vn, tl_new, kvp=KVP, rr_block=RR)
    with set_mesh(mesh):
        out_u = jax.jit(lambda q, k, v: helix_attention(
            mesh, hx_pl, q, k, v, tl_new))(q, kc_u, vc_u)
        out_f, kc_f, vc_f = jax.jit(
            lambda q, k, v, kn, vn: helix_attention(
                mesh, hx_pl, q, k, v, tl_new, k_new=kn, v_new=vn))(
                    q, k_rr, v_rr, kn, vn)
    np.testing.assert_array_equal(np.asarray(out_f), np.asarray(out_u))
    np.testing.assert_array_equal(np.asarray(kc_f), np.asarray(kc_u))
    np.testing.assert_array_equal(np.asarray(vc_f), np.asarray(vc_u))
print("fused KV-append epilogue == unfused (KVP=8, scalar + [B] tl): OK")

# ---- shared-pool paged KV == fixed-cap layout through the KVP=8 shard_map ----
from repro.core.kvcache import cache_to_pages, pages_to_cache
hx_bs = dataclasses.replace(hx, attn_block_s=RR)          # align partitions
hx_bs_pl = dataclasses.replace(hx_pl, attn_block_s=RR)
BS = KVP * RR                                             # positions / page
MP = S_CAP // BS
NPOOL = 1 + B * MP
tbl = np.zeros((B, MP), np.int32)
perm = np.random.default_rng(5).permutation(np.arange(1, NPOOL))
pool_k = jnp.zeros((NPOOL, KH, BS, HSZ), jnp.float32)
pool_v = jnp.zeros((NPOOL, KH, BS, HSZ), jnp.float32)
pi = 0
for b in range(B):
    pk_pages = cache_to_pages(k_rr[b][None], KVP, BS)[0]
    pv_pages = cache_to_pages(v_rr[b][None], KVP, BS)[0]
    for p in range(MP):
        phys = int(perm[pi]); pi += 1
        tbl[b, p] = phys
        pool_k = pool_k.at[phys].set(pk_pages[p])
        pool_v = pool_v.at[phys].set(pv_pages[p])
tbl = jnp.asarray(tbl)
# pallas also at two pages an S-block (fixed at the same 2*RR slots)
hx_bs2_pl = dataclasses.replace(hx_pl, attn_block_s=2 * RR)
for hxf, hxp_base in ((hx_bs, hx_bs), (hx_bs_pl, hx_bs_pl),
                      (hx_bs2_pl, hx_bs2_pl)):
    hxp = dataclasses.replace(hxp_base, paged_kv=True)
    for tl_case, win in ((total_len, 0), (tls, 0), (tls, 64)):
        with set_mesh(mesh):
            of = jax.jit(lambda q, k, v: helix_attention(
                mesh, hxf, q, k, v, tl_case, window=win))(q, k_rr, v_rr)
            op = jax.jit(lambda q, k, v, t: helix_attention(
                mesh, hxp, q, k, v, tl_case, window=win,
                block_tables=t))(q, pool_k, pool_v, tbl)
        np.testing.assert_array_equal(np.asarray(of), np.asarray(op))
print("paged pool == fixed (KVP=8, ref + pallas at 1 and 2 pages a "
      "block, windowed, [B] tl): OK")

# paged fused append == fixed fused append (pool planes reassemble exactly)
kn_p = jnp.asarray(rng.standard_normal((B, KH, HSZ), np.float32))
vn_p = jnp.asarray(rng.standard_normal((B, KH, HSZ), np.float32))
tl_pp = jnp.asarray([201, 38, 151, 10], jnp.int32)
hxp = dataclasses.replace(hx_bs_pl, paged_kv=True)
with set_mesh(mesh):
    out_ff, kc_ff, vc_ff = jax.jit(lambda q, k, v, kn, vn: helix_attention(
        mesh, hx_bs_pl, q, k, v, tl_pp, k_new=kn, v_new=vn))(
            q, k_rr, v_rr, kn_p, vn_p)
    out_fp, pk_fp, pv_fp = jax.jit(
        lambda q, k, v, kn, vn, t: helix_attention(
            mesh, hxp, q, k, v, tl_pp, k_new=kn, v_new=vn,
            block_tables=t))(q, pool_k, pool_v, kn_p, vn_p, tbl)
np.testing.assert_array_equal(np.asarray(out_ff), np.asarray(out_fp))
tbl_np = np.asarray(tbl)
got_k = jnp.stack([pages_to_cache(pk_fp[tbl_np[b]][None], KVP)[0]
                   for b in range(B)])
got_v = jnp.stack([pages_to_cache(pv_fp[tbl_np[b]][None], KVP)[0]
                   for b in range(B)])
np.testing.assert_array_equal(np.asarray(got_k), np.asarray(kc_ff))
np.testing.assert_array_equal(np.asarray(got_v), np.asarray(vc_ff))
print("paged fused KV-append == fixed (KVP=8 shard_map): OK")

# ---- grouped shared-prefix decode == ungrouped through the KVP=8 shard_map ----
# rows 0,1 map the same first physical page (a shared prefix in the pool);
# the two-pass grouped kernel must match the ungrouped sweep over the same
# tables, including windowed and fused-append modes: the same blocks in the
# same order, but the prefix pass multiplies a group's stacked query rows in
# one matmul, and XLA:CPU picks its dot kernel by row count, so outputs
# agree to f32 rounding (observed <= 6e-8); appended caches bit for bit.
GROUPED_TOL = dict(rtol=1e-6, atol=1e-6)
tbl2_np = np.asarray(tbl).copy()
tbl2_np[1, 0] = tbl2_np[0, 0]
tbl2 = jnp.asarray(tbl2_np)
gid_g = jnp.asarray([0, 0, 2, 3], jnp.int32)
gnp_g = jnp.asarray([1, 1, 0, 0], jnp.int32)   # 1 shared page; 2 singletons
tls2 = jnp.asarray([200, 150, 200, 129], jnp.int32)
with set_mesh(mesh):
    for win in (0, 64):
        ou = jax.jit(lambda q, k, v, t: helix_attention(
            mesh, hxp, q, k, v, tls2, window=win, block_tables=t))(
                q, pool_k, pool_v, tbl2)
        og = jax.jit(lambda q, k, v, t, g, n: helix_attention(
            mesh, hxp, q, k, v, tls2, window=win, block_tables=t,
            groups=(g, n)))(q, pool_k, pool_v, tbl2, gid_g, gnp_g)
        np.testing.assert_allclose(np.asarray(og), np.asarray(ou),
                                   **GROUPED_TOL)
    of, kf, vf = jax.jit(lambda q, k, v, kn, vn, t: helix_attention(
        mesh, hxp, q, k, v, tls2 + 1, k_new=kn, v_new=vn, block_tables=t))(
            q, pool_k, pool_v, kn_p, vn_p, tbl2)
    og2, kg2, vg2 = jax.jit(lambda q, k, v, kn, vn, t, g, n: helix_attention(
        mesh, hxp, q, k, v, tls2 + 1, k_new=kn, v_new=vn, block_tables=t,
        groups=(g, n)))(q, pool_k, pool_v, kn_p, vn_p, tbl2, gid_g, gnp_g)
np.testing.assert_allclose(np.asarray(og2), np.asarray(of), **GROUPED_TOL)
np.testing.assert_array_equal(np.asarray(kg2), np.asarray(kf))
np.testing.assert_array_equal(np.asarray(vg2), np.asarray(vf))
print("grouped shared-prefix == ungrouped (KVP=8, windowed + fused append): OK")

# ---- chunked prefill == one-shot prefill through the KVP=8 shard_map ----
# tokens agree exactly; caches to f32 rounding of the K/V projection, whose
# XLA:CPU dot kernel depends on the chunk's row count (as in
# tests/serving/test_chunked_prefill_exact.py)
CHUNK_TOL = dict(rtol=1e-5, atol=1e-5)
from repro.configs import get_config
from repro.models.model_zoo import (build_serve_step, finalize_chunked_prefill,
                                    init_prefill_buffers,
                                    make_chunk_prefill_step, make_prefill_step)
from repro.models.transformer import init_params

cfg = get_config("granite-3-2b").reduced()
params = init_params(cfg, jax.random.PRNGKey(0))
hx_m = HelixConfig(kvp_axes=("data", "model"), tpa_axis=None)
T, CAP = 40, 128                       # cache_capacity(40, kvp=8, rr=16)
toks = jax.random.randint(jax.random.PRNGKey(2), (1, T), 0, cfg.vocab)
with set_mesh(mesh):
    prefill = jax.jit(make_prefill_step(cfg, mesh, hx_m, s_cap=CAP))
    last_logits, st1 = prefill(params, {"tokens": toks})
    tok1 = int(jnp.argmax(last_logits[0, :cfg.vocab]))
    chunk_step = jax.jit(make_chunk_prefill_step(cfg, mesh, hx_m))
    for chunk in (17, T):
        bufs = init_prefill_buffers(cfg, 1, T, tp_width=mesh.shape["model"])
        pos = 0
        while pos < T:
            c = min(chunk, T - pos)
            nt, bufs = chunk_step(params, toks[:, pos:pos + c], bufs,
                                  jnp.asarray(pos, jnp.int32))
            pos += c
        st2 = finalize_chunked_prefill(cfg, hx_m, bufs, T, s_cap=CAP, kvp=8)
        assert int(nt[0, -1]) == tok1, (chunk, int(nt[0, -1]), tok1)
        np.testing.assert_allclose(np.asarray(st2["kcache"]),
                                   np.asarray(st1["kcache"]), **CHUNK_TOL)
        np.testing.assert_allclose(np.asarray(st2["vcache"]),
                                   np.asarray(st1["vcache"]), **CHUNK_TOL)
        # decode continuation agrees step for step (tokens + caches)
        serve = jax.jit(build_serve_step(cfg, mesh, hx_m))
        cur1 = cur2 = jnp.full((1,), tok1, jnp.int32)
        s1, s2 = dict(st1), dict(st2)
        for _ in range(2):
            cur1, s1 = serve(params, s1, cur1)
            cur2, s2 = serve(params, s2, cur2)
            assert int(cur1[0]) == int(cur2[0])
        np.testing.assert_allclose(np.asarray(s2["kcache"]),
                                   np.asarray(s1["kcache"]), **CHUNK_TOL)
print("chunked prefill == one-shot (KVP=8 shard_map, chunk 17/T): OK")

# ---- append_kv round-robin ----
kc = jnp.zeros((B, KH, S_CAP, HSZ))
vc = jnp.zeros((B, KH, S_CAP, HSZ))
for pos in range(40):
    kn = jnp.full((B, KH, HSZ), float(pos + 1))
    kc, vc = append_kv(kc, vc, kn, kn, pos + 1, kvp=KVP, rr_block=RR)
# slot check: position p -> value p+1
for r in range(KVP):
    pos_map = np.asarray(shard_positions(32, r, KVP, RR))
    local = np.asarray(kc[0, 0, r*32:(r+1)*32, 0])
    expect = np.where(pos_map < 40, pos_map + 1, 0)
    np.testing.assert_array_equal(local, expect)
print("append_kv round-robin layout: OK")
print("ALL OK")
