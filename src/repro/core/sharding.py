"""Phase-dependent sharding policies (Helix's "re-provisioning", §2.2).

One *fixed* device mesh; the **logical role** of its axes changes per phase:

  train/prefill :  DP = ("pod","data")   TP = ("model",)   EP = ("data",)
  helix decode  :  KVP × TPA during attention, TPF(×EP) during FFN — these
                   live inside shard_map (core/helix.py, models/decode_model);
                   this module provides the in/out PartitionSpecs for params,
                   caches and batch data.

This is the TPU-idiomatic equivalent of the paper's GPU pool
reconfiguration: meshes are static under XLA, so "re-provisioning" is
re-interpreting axis roles (DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig


# ------------------------------------------------------------------ policy
class MeshPolicy:
    """Callable activation-sharding policy for the GSPMD (train/prefill) path.

    ``policy(x, "dp", None, "tp")`` constrains x's dims to the mesh axes the
    logical roles map to.  Unknown/None dims stay unconstrained.
    """

    def __init__(self, mesh: Mesh, roles: dict[str, tuple[str, ...]]):
        self.mesh = mesh
        self.roles = roles

    def spec(self, *axes) -> P:
        return P(*[self.roles.get(a) if a else None for a in axes])

    def __call__(self, x, *axes):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.spec(*axes)))

    def per_shard(self, fn, args, axes, out_axes):
        """``fn(*args)`` run on each device's shard under ``shard_map``.

        GSPMD cannot partition a Pallas kernel, so its caller splits the
        operands explicitly.  ``axes[i]`` names argument i's logical role
        per dim (as for ``__call__``), ``out_axes`` the result's.  A role
        whose mesh axes do not divide every dim it labels (a packed prefill
        of 3 requests over 4 data shards) is replicated instead."""
        import math

        from repro.utils import shard_map
        dims: dict[str, list[int]] = {}
        for x, ax in zip(args, axes):
            for d, role in zip(jax.numpy.shape(x), ax):
                if role:
                    dims.setdefault(role, []).append(d)
        keep = {r for r, ds in dims.items()
                if all(d % math.prod(self.mesh.shape[a]
                                     for a in self.roles.get(r, ())) == 0
                       for d in ds)}

        def spec(ax):
            return self.spec(*[a if a in keep else None for a in ax])

        return shard_map(fn, mesh=self.mesh,
                         in_specs=tuple(spec(ax) for ax in axes),
                         out_specs=spec(out_axes), check_vma=False)(*args)


def train_roles(mesh: Mesh) -> dict[str, tuple[str, ...]]:
    names = mesh.axis_names
    dp = tuple(n for n in names if n in ("pod", "data"))
    roles = {"dp": dp, "tp": ("model",), "ep": ("data",)}
    if "pod" in names:
        roles["pod"] = ("pod",)
    return roles


# ------------------------------------------------------------------ helix
@dataclasses.dataclass(frozen=True)
class HelixConfig:
    """How the mesh axes are consumed by the Helix decode phases.

    Attention phase: KV cache sharded over kvp_axes (sequence, round-robin)
    × tpa_axis (kv heads, requires TPA <= K).  FFN phase: same devices as
    TPF = everything (dense) or TPF × EP (MoE, EP = ep_axis).

    Kernel backends: the four ``*_backend`` fields select, per kernel family,
    one of ``"ref"`` | ``"pallas-interpret"`` | ``"pallas"`` from the unified
    registry (kernels/registry.py); left unset (None) they take
    ``registry.default_backend`` — compiled ``pallas`` on a TPU, ``ref``
    elsewhere.  ``attn_backend`` routes flash_decode
    (the Helix decode attention inside the shard_map), ``prefill_backend``
    routes flash_prefill (full-sequence attention in prefill/train),
    ``ssd_backend`` routes ssd_prefill (the Mamba2 SSD scan core) and
    ``matmul_backend`` routes w8a16_matmul.  All backends of a family are
    exact up to fp summation order; see docs/kernels.md.
    """
    kvp_axes: tuple[str, ...]            # sequence-sharding axes
    tpa_axis: str | None = None          # head-sharding axis (None => TPA=1)
    ep_axis: str | None = None           # expert axis during FFN (MoE)
    rr_block: int = 16                   # §2.3 round-robin block
    # --- beyond-paper §Perf knobs (paper-faithful defaults) ---
    qkv_shard: bool = False              # shard QKV weights over 'model' and
    #   all-gather the small activations, instead of the paper's replicated
    #   per-rank QKV compute (wins when decode is weight-read bound)
    kv_cache_bits: int = 16              # 8 => int8 KV cache + f32 scales
    paged_kv: bool = False               # shared-pool paged KV cache: K/V
    #   live in [L, n_blocks, Kh, block_s, hsz] pool planes with per-request
    #   block tables instead of fixed per-slot rows, so cache pressure is a
    #   *global* page count (serving/pool.py, core/kvcache.py paged layout).
    #   Bit-exact vs the fixed layout at the same attn_block_s partition;
    #   decode-state leaves gain `block_tables` [B, max_pages] int32.
    attn_block_s: int = 512              # flash_decode S-block size (kernel
    #   tuning knob; clamped to the shard capacity).  In paged mode a block
    #   is the whole per-rank pages that fit it (at least one, at most the
    #   table), gathered through the block table: at an attn_block_s that is
    #   a multiple of the page rows, fixed and paged online-softmax block
    #   partitions are identical, hence bit-exact parity between the layouts.
    # --- per-family kernel backends (kernels/registry.py); None = the
    # platform default (registry.default_backend) ---
    attn_backend: str | None = None      # flash_decode (helix decode attn)
    prefill_backend: str | None = None   # flash_prefill (prefill/train attn)
    ssd_backend: str | None = None       # ssd_prefill (mamba2 SSD core)
    matmul_backend: str | None = None    # w8a16_matmul (int8-weight matmul)
    fuse_append: bool = True             # fuse the rr-slot KV append into the
    #   flash-decode kernel epilogue (saves one cache HBM round-trip per
    #   layer per step).  Only active on the pallas backends, for round-robin
    #   caches (fp and int8 — the kernel quantizes the new token in-kernel);
    #   set False to force the separate append_kv pass (bit-exact either way).
    prune_blocks: bool = True            # length/causality-aware block
    #   pruning in the Pallas attention kernels: invalid K/V blocks are
    #   *skipped* (index_map clamp elides their DMAs), not masked, so
    #   per-request HBM reads scale with the true sequence length (and the
    #   window on sliding-window layers) instead of the slot capacity.
    #   Bit-exact either way; False restores the dense sweep (and, on the
    #   Pallas backends, re-enables the caller-side windowed cache-slice
    #   fast path the pruning subsumes).
    lm_head_w8: bool = False             # quantize the lm_head weights to
    #   int8 (per-column symmetric) on the decode path and run the logits
    #   matmul through the w8a16_matmul family (``matmul_backend`` picks the
    #   oracle or the Pallas kernel).  Changes numerics (weight-only
    #   quantization); all matmul_backend choices agree on the same
    #   quantized weights up to fp summation order.
    grouped_decode: bool = False         # grouped shared-prefix decode
    #   (CoDec-style, arXiv 2505.17694) on the paged Pallas backends:
    #   requests whose block tables share leading pages (prefix sharing —
    #   serving/pool.py) stack their Q rows and stream each shared page
    #   once per *group* instead of once per request.  Requires paged_kv;
    #   decode-state leaves gain `group_id`/`group_np` [B] int32 (the
    #   engine recomputes them each step).  Bit-exact vs ungrouped; the
    #   ref backend ignores the grouping (oracle semantics).

    def __post_init__(self):
        from repro.kernels import registry
        for field, family in registry.FAMILY_FIELDS.items():
            if getattr(self, field) is None:
                object.__setattr__(self, field,
                                   registry.default_backend(family))
            assert getattr(self, field) in registry.BACKENDS, \
                (field, getattr(self, field), registry.BACKENDS)

    def backend_for(self, family: str) -> str:
        """Selected backend for a registry kernel family name."""
        from repro.kernels import registry
        for field, fam in registry.FAMILY_FIELDS.items():
            if fam == family:
                return getattr(self, field)
        raise ValueError(f"unknown kernel family {family!r}")

    def all_axes(self) -> tuple[str, ...]:
        """Every mesh axis the attention phase consumes (kvp then tpa)."""
        return self.kvp_axes + ((self.tpa_axis,) if self.tpa_axis else ())

    def kvp(self, mesh: Mesh) -> int:
        """KV-parallel width: product of the kvp axes' sizes on ``mesh``."""
        import math
        return math.prod(mesh.shape[a] for a in self.kvp_axes)

    def tpa(self, mesh: Mesh) -> int:
        """Attention tensor-parallel width (1 when ``tpa_axis`` is None)."""
        return mesh.shape[self.tpa_axis] if self.tpa_axis else 1


def default_helix_config(cfg: ArchConfig, mesh: Mesh) -> HelixConfig:
    """Paper §2.1: TPA <= K, KVP = rest.  Pure-KVP (TPA=1) is roofline-
    equivalent for KV reads (DESIGN.md §2 mesh-shape constraint); archs with
    K >= model-width use the 2-D mode (phi-3-vision: TPA=model)."""
    names = mesh.axis_names
    model_w = mesh.shape["model"]
    ep = "data" if cfg.moe else None
    if cfg.has_attention and cfg.n_kv_heads >= model_w:
        kvp = tuple(n for n in names if n != "model")
        return HelixConfig(kvp_axes=kvp, tpa_axis="model", ep_axis=ep)
    return HelixConfig(kvp_axes=tuple(names), tpa_axis=None, ep_axis=ep)


# --------------------------------------------------------- param specs
def _match(tree: Any, fn) -> Any:
    """tree_map over dict-of-arrays with (path, leaf) callback."""
    return {
        k: _match(v, lambda p, x, k=k: fn((k,) + p, x)) if isinstance(v, dict)
        else fn((k,), v)
        for k, v in tree.items()
    }


def _sized(mesh: Mesh):
    """dim-size-aware spec guard: axes kept only if they divide the dim."""
    def ok(dim_size: int, axes) -> Any:
        if axes is None:
            return None
        tup = (axes,) if isinstance(axes, str) else tuple(axes)
        import math
        return axes if dim_size % math.prod(
            mesh.shape[a] for a in tup) == 0 else None
    return ok


def train_param_specs(cfg: ArchConfig, params, mesh: Mesh) -> Any:
    """GSPMD train/prefill specs: Megatron TP over 'model', experts over
    'data' (EP), everything else replicated.  Layer-stacked leaves keep a
    leading None dim.  Axes that don't divide a dim fall back to replicated
    (pjit argument shardings must divide evenly)."""
    ok = _sized(mesh)

    def leaf(path, x):
        name = path[-1]
        stacked = path[0] in ("layers",) or (path[0] == "enc"
                                             and path[1] == "layers")
        lead = (None,) if stacked else ()
        nd = x.ndim - len(lead)
        if name in ("wq", "wk", "wv", "w1", "w3"):       # col-parallel
            if len(path) >= 2 and path[-2] == "moe":
                return P(*lead, ok(x.shape[1], "data"), None,
                         ok(x.shape[3], "model"))        # [L,E,H,Fe]
            return P(*lead, None, ok(x.shape[-1], "model"))
        if name in ("wo", "w2"):                          # row-parallel
            if len(path) >= 2 and path[-2] == "moe":
                return P(*lead, ok(x.shape[1], "data"),
                         ok(x.shape[2], "model"), None)  # [L,E,Fe,H]
            return P(*lead, ok(x.shape[-2], "model"), None)
        if name == "router":
            return P(*lead, None, None)
        if name == "w_in":                                # ssm in-proj
            return P(*lead, None, ok(x.shape[-1], "model"))
        if name == "w_out":
            return P(*lead, ok(x.shape[-2], "model"), None)
        if name in ("conv_w", "conv_b", "norm_w", "A_log", "D", "dt_bias"):
            if nd >= 1:
                return P(*lead, ok(x.shape[len(lead)], "model"),
                         *([None] * (nd - 1)))
            return P()
        if name == "embed":
            return P(ok(x.shape[0], "model"), None)
        if name == "lm_head":
            return P(None, ok(x.shape[1], "model"))
        return P(*lead, *([None] * nd))

    return _match(params, leaf)


def dense_ffn_mode(cfg: ArchConfig, mesh: Mesh, hx: HelixConfig) -> str:
    """'1d' — TPF = N on the F dim (the paper's dense layout); '2d' — H over
    the dp-ish axes × F over 'model' when F doesn't divide by N (hymba's
    F=5504, arctic multi-pod residual F=4864)."""
    import math
    n = math.prod(mesh.shape[a] for a in _axes(hx))
    return "1d" if cfg.d_ff % n == 0 else "2d"


def helix_param_specs(cfg: ArchConfig, params, hx: HelixConfig,
                      mesh: Mesh) -> Any:
    """Decode-phase specs (GSPMD argument shardings for serve_step).

    FFN weights: TPF = all axes (dense, '1d' mode; '2d' fallback shards
    H x F) or EP=data × TPF=rest (MoE experts).  Attention QKV: sharded over
    tpa_axis heads only (replicated over KVP — the paper's choice: every KVP
    rank computes the full QKV projection).  wo: input dim sharded over ALL
    axes (the post-all-to-all [B, H/N] layout, tpa-major then kvp) when it
    divides; 'model'-on-H fallback for padded flat dims (see helix_out_dim).
    """
    import math
    ok = _sized(mesh)
    tpf = tuple(a for a in ("pod", "model") if a in _axes(hx)) or None
    all_ax = _axes(hx)
    n_all = math.prod(mesh.shape[a] for a in all_ax)
    o_in = ((hx.tpa_axis,) if hx.tpa_axis else ()) + hx.kvp_axes
    ffn2d = cfg.d_ff and dense_ffn_mode(cfg, mesh, hx) == "2d"
    dp_ish = tuple(a for a in mesh.axis_names if a != "model")

    def leaf(path, x):
        name = path[-1]
        stacked = path[0] in ("layers",) or (path[0] == "enc"
                                             and path[1] == "layers")
        lead = (None,) if stacked else ()
        nd = x.ndim - len(lead)
        moe = len(path) >= 2 and path[-2] == "moe"
        if moe and name in ("w1", "w3"):
            return P(*lead, ok(x.shape[1], hx.ep_axis), None,
                     ok(x.shape[3], tpf))
        if moe and name == "w2":
            return P(*lead, ok(x.shape[1], hx.ep_axis),
                     ok(x.shape[2], tpf), None)
        if moe and name == "router":
            return P(*lead, None, None)
        if name in ("w1", "w3"):                          # dense FFN
            if ffn2d:
                return P(*lead, ok(x.shape[-2], dp_ish),
                         ok(x.shape[-1], "model"))
            return P(*lead, None, all_ax)
        if name == "w2":
            if ffn2d:
                return P(*lead, ok(x.shape[-2], "model"),
                         ok(x.shape[-1], dp_ish))
            return P(*lead, all_ax, None)
        if name in ("wq", "wk", "wv"):
            if hx.qkv_shard and not hx.tpa_axis:
                return P(*lead, None, ok(x.shape[-1], "model"))
            return P(*lead, None, ok(x.shape[-1], hx.tpa_axis)
                     if hx.tpa_axis else None)
        if name == "wo":
            # input dim == q_dim; shardable over all axes iff divisible
            return P(*lead, ok(x.shape[-2], o_in), None)
        if name == "w_in":                        # ssm: TP over 'model' only
            return P(*lead, None, ok(x.shape[-1], "model"))
        if name == "w_out":
            return P(*lead, ok(x.shape[-2], "model"), None)
        if name in ("conv_w", "conv_b", "norm_w", "A_log", "D", "dt_bias"):
            if nd >= 1:
                return P(*lead, ok(x.shape[len(lead)], "model"),
                         *([None] * (nd - 1)))
            return P()
        if name == "embed":
            return P(ok(x.shape[0], "model"), None)   # lookup-friendly
        if name == "lm_head":
            return P(None, ok(x.shape[1], all_ax))
        return P(*lead, *([None] * nd))

    return _match(params, leaf)


def _axes(hx: HelixConfig) -> tuple[str, ...]:
    return hx.all_axes()


def cache_specs(hx: HelixConfig):
    """KV cache [L, B, Kh/TPA, S/KVP, hsz]: sequence over kvp, heads over tpa."""
    return P(None, None, hx.tpa_axis, hx.kvp_axes, None)


def to_shardings(mesh: Mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
