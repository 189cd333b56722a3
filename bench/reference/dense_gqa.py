"""Plain float32 reference of a dense GQA decoder, and the seeded weights.

The architecture, as a configuration file under ``bench/configs`` states
it (Hugging Face key names): token embedding; per layer
``x += Wo·attn(rope(Wq·n1(x)), rope(Wk·n1(x)), Wv·n1(x))`` and
``x += W2·(silu(W1·n2(x)) * W3·n2(x))``; a final norm; logits against the
tied embedding or an untied head.  Norms are RMSNorm with a ``1 + w``
gain; RoPE rotates the two halves of each head; query head ``i`` reads
kv head ``i // (heads / kv_heads)``; attention is causal.  Granite's
scalars apply where the file gives them: ``embedding_multiplier`` on the
embedding, ``attention_multiplier`` as the softmax scale (else
``head_dim ** -0.5``), ``residual_multiplier`` on each branch and
``logits_scaling`` as the logits' divisor.

Nothing here imports the program.  ``init_weights`` fills the parameter
tree the server takes (its leaf names and shapes are passed in) from the
seed in one jitted call; the reference reads the same tree by name and
computes in float32 at the highest matmul precision, one layer at a time
and in blocks of query rows, so that a long context fits beside the
served weights.  ``quant="fp8"`` is the control: every matmul operand,
attention's q, k, v and probabilities included, rounded to float8 e4m3
(per-tensor scale for weights, per-row for activations), the step below
the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


# ------------------------------------------------------------- weights
def _leaf_scale(path: tuple[str, ...], shape, n_layers: int):
    """(kind, std) of one parameter leaf, by its name."""
    name = path[-1]
    if name.startswith("ln"):
        return "norm", 0.1            # gain 1 + w: a norm bug shows
    if name == "embed":
        return "normal", 0.02
    fan_in = shape[-2]
    std = fan_in ** -0.5
    if name in ("wo", "w2"):
        std /= math.sqrt(2 * n_layers)
    return "normal", std


def init_weights(c: dict, seed: int, shapes, dtype, out_shardings=None):
    """Random weights for every leaf of ``shapes`` (a pytree of
    ShapeDtypeStructs, keyed as the server names its parameters), made on
    the device by one jitted call from ``seed``, in ``dtype``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [tuple(getattr(k, "key", str(k)) for k in p) for p, _ in flat]
    specs = [_leaf_scale(p, s.shape, c["num_hidden_layers"])
             for p, (_, s) in zip(paths, flat)]
    dims = [tuple(s.shape) for _, s in flat]

    def make(seed):
        key = jax.random.PRNGKey(seed)
        out = []
        for i, ((_, std), shape) in enumerate(zip(specs, dims)):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * std
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make, out_shardings=out_shardings)(
        np.uint32(seed % 2 ** 32))


# ----------------------------------------------------------- reference
def _q8(x, axis=None):
    """Round ``x`` to float8 e4m3 with an absmax scale (per tensor, or per
    slice along ``axis``), back in float32."""
    m = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(m, 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, quant):
    """Activation rows ``a`` [.., K] times weight ``w`` [K, N], float32."""
    if quant == "fp8":
        a, w = _q8(a, axis=-1), _q8(w)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, pos, theta):
    """x [T, heads, hsz], pos [T]: rotate the two halves of each head."""
    hsz = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hsz, 2, dtype=jnp.float32) / hsz))
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _row_block(t: int, width: int, budget: int = 1 << 27) -> int:
    """Rows per block so that a [rows, width] float32 block stays under
    ``budget`` elements (and divides ``t``)."""
    b = 1
    while b * 2 <= t and t % (b * 2) == 0 and b * 2 * width <= budget:
        b *= 2
    return b


def _layer(c, lp, x, pos, quant):
    t, d = x.shape
    qh, kh = c["num_attention_heads"], c["num_key_value_heads"]
    hsz = c.get("head_dim") or d // qh
    g = qh // kh
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    scale = c.get("attention_multiplier") or hsz ** -0.5
    res = c.get("residual_multiplier") or 1.0
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    a = lp["attn"]
    h = _rms(x, lp["ln1"], eps)
    q = _rope(_mm(h, a["wq"], quant).reshape(t, qh, hsz), pos, theta)
    k = _rope(_mm(h, a["wk"], quant).reshape(t, kh, hsz), pos, theta)
    v = _mm(h, a["wv"], quant).reshape(t, kh, hsz)
    qb = _row_block(t, qh * t, budget=1 << 26)

    def attend(j):
        qj = jax.lax.dynamic_slice_in_dim(q, j * qb, qb).reshape(
            qb, kh, g, hsz)
        kk, vv = k, v
        if quant == "fp8":
            qj, kk, vv = _q8(qj, -1), _q8(k, -1), _q8(v, -1)
        s = jnp.einsum("qkgd,skd->kgqs", qj, kk,
                       precision=HIGHEST) * scale
        qpos = j * qb + jnp.arange(qb)
        s = jnp.where(qpos[:, None] >= jnp.arange(t)[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quant == "fp8":
            p = _q8(p, -1)
        o = jnp.einsum("kgqs,skd->qkgd", p, vv, precision=HIGHEST)
        return o.reshape(qb, qh * hsz)

    o = jax.lax.map(attend, jnp.arange(t // qb)).reshape(t, qh * hsz)
    x = x + res * _mm(o, a["wo"], quant)
    f = lp["ffn"]
    fb = _row_block(t, f["w1"].shape[-1])

    def ffn(xb):
        h2 = _rms(xb, lp["ln2"], eps)
        return _mm(jax.nn.silu(_mm(h2, f["w1"], quant))
                   * _mm(h2, f["w3"], quant), f["w2"], quant)

    y = jax.lax.map(ffn, x.reshape(t // fb, fb, d)).reshape(t, d)
    return x + res * y


@functools.partial(jax.jit, static_argnums=(0, 4))
def _logits_rows(ckey, params, tokens, rows, quant):
    c = dict(ckey)
    x = params["embed"][tokens].astype(jnp.float32) \
        * (c.get("embedding_multiplier") or 1.0)
    pos = jnp.arange(tokens.shape[0])

    def body(x, lp):
        return _layer(c, lp, x, pos, quant), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _rms(x[rows], params["ln_f"].astype(jnp.float32), c["rms_norm_eps"])
    if "lm_head" in params:
        head = params["lm_head"].astype(jnp.float32)
    else:
        head = params["embed"].astype(jnp.float32).T
    return _mm(x, head, quant)[:, :c["vocab_size"]] \
        / (c.get("logits_scaling") or 1.0)


REF_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "vocab_size",
            "attention_multiplier", "embedding_multiplier",
            "residual_multiplier", "logits_scaling")


SEQ_LADDER = (1024, 2048, 4096, 6144, 8192, 12288, 16384, 24576, 32768,
              49152, 65536)
ROW_LADDER = (64, 128, 256, 512, 1024, 2048, 4096, 8192)


def _bucket(n: int, ladder) -> int:
    for b in ladder:
        if n <= b:
            return b
    raise ValueError(f"{n} is past the reference's largest size {ladder[-1]}")


def logits_at(c: dict, params, tokens, rows, quant=None):
    """Reference logits [len(rows), vocab] of one sequence ``tokens`` at
    positions ``rows``.  The sequence and the rows are padded to the next
    size of a fixed ladder, so that a cell compiles one or two programs
    whatever its seed; padding sits after every real position, which
    causal attention never reads."""
    n, r = len(tokens), len(rows)
    t = _bucket(n, SEQ_LADDER)
    rp = _bucket(r, ROW_LADDER)
    tok = np.zeros((t,), np.int32)
    tok[:n] = tokens
    idx = np.zeros((rp,), np.int32)
    idx[:r] = rows
    ckey = tuple((k, c.get(k)) for k in REF_KEYS)
    with jax.default_matmul_precision("highest"):
        out = _logits_rows(ckey, params, jnp.asarray(tok), jnp.asarray(idx),
                           quant)
    return np.asarray(out)[:r]


def served_gaps(ref_logits, tokens) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best, in units of that row's standard deviation."""
    ref = np.asarray(ref_logits, np.float64)
    tok = np.asarray(tokens)
    best = ref.max(-1)
    mine = ref[np.arange(len(tok)), tok]
    return (best - mine) / np.maximum(ref.std(-1), 1e-30)
