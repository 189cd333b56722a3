"""Jaxpr collective/dtype auditor for the serving hot path.

Traces the decode/prefill step builders (``jax.make_jaxpr`` on a 1x1 mesh —
collective equations are recorded even at axis size 1) and walks every
equation, recursing through ``scan``/``shard_map``/``pjit`` sub-jaxprs, to
assert the HOP-B dataflow of §3 of the paper:

  collective.count  exactly one KVP combine per attention layer — one
                    ``all_to_all`` (the TPA resharding of output fragments)
                    plus one ``all_gather`` (the LSE exchange) over the KVP
                    axes, and no stray ``psum`` over them.  A duplicated
                    combine doubles the per-token communication the paper's
                    TTL model budgets; a missing one is a miscompile.
  collective.axis   every collective names only mesh axes, and the
                    attention combines run over exactly the KVP axes.
  dtype.upcast      no fp64 values anywhere in the traced step, and the
                    decode-state leaves (KV cache, SSM state) keep their
                    dtypes through the step (``jax.eval_shape``) — a silent
                    int8 -> f32 cache upcast would 4x the paper's KV-cache
                    DRAM term.

``run_jaxpr_audit`` applies this to the real serving graphs:
``build_serve_step`` (decode, expects combines == attention sublayers per
scan period) and ``make_prefill_step`` (expects zero collectives — prefill
shards KV-free over data/model via GSPMD constraints only).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.findings import Finding, Report

# psum traced inside a replication-checked shard_map is the
# ``psum_invariant`` primitive (with an ``axes`` param instead of
# ``axis_name``) — normalized back to "psum" in collect_collectives so
# expected-count specs stay primitive-name based
_COMBINE_PRIMS = ("all_to_all", "all_gather", "psum", "psum_invariant")


def _axis_tuple(val) -> tuple:
    if val is None:
        return ()
    if isinstance(val, (tuple, list)):
        return tuple(val)
    return (val,)


def collect_collectives(jaxpr, path="") -> list[dict]:
    """Flatten every collective equation in ``jaxpr`` (recursing through
    scan/shard_map/pjit/custom-call sub-jaxprs).

    Returns dicts ``{"prim", "axes", "path"}`` — ``axes`` the normalized
    axis-name tuple, ``path`` the equation trail (e.g.
    ``scan/shard_map/all_to_all``) for findings messages.
    """
    out = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        here = f"{path}/{name}" if path else name
        if name in _COMBINE_PRIMS or name == "axis_index":
            axes = _axis_tuple(eqn.params.get("axis_name",
                                              eqn.params.get("axes")))
            prim = "psum" if name == "psum_invariant" else name
            out.append({"prim": prim, "axes": axes, "path": here})
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None and hasattr(sub, "eqns"):
                out.extend(collect_collectives(sub, here))
            elif hasattr(v, "eqns"):
                out.extend(collect_collectives(v, here))
    return out


def _walk_dtypes(jaxpr, bad, path=""):
    for eqn in jaxpr.eqns:
        here = f"{path}/{eqn.primitive.name}" if path else eqn.primitive.name
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and dt in (jnp.float64, np.complex128):
                bad.append((here, str(dt)))
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None and hasattr(sub, "eqns"):
                _walk_dtypes(sub, bad, here)
            elif hasattr(v, "eqns"):
                _walk_dtypes(v, bad, here)


def audit_step_fn(fn, args, *, kvp_axes, mesh_axes, expected, where,
                  symbol) -> list[Finding]:
    """Audit one traced step function.

    ``expected`` maps combine primitive -> required count over the KVP
    axes (e.g. ``{"all_to_all": 1, "all_gather": 1, "psum": 0}``).
    ``kvp_axes``/``mesh_axes`` are axis-name tuples; ``where``/``symbol``
    locate the findings.  Returns the findings (empty = clean).
    """
    findings = []
    try:
        jaxpr = jax.make_jaxpr(fn)(*args)
    except Exception as e:
        return [Finding(check="collective.count", path=where, symbol=symbol,
                        message=f"step function failed to trace: {e!r}")]
    colls = collect_collectives(jaxpr.jaxpr)
    kvp = set(kvp_axes)
    mesh = set(mesh_axes)

    for c in colls:
        unknown = set(c["axes"]) - mesh
        if unknown:
            findings.append(Finding(
                check="collective.axis", path=where, symbol=symbol,
                message=f"{c['path']}: collective over non-mesh axes "
                        f"{sorted(unknown)} (mesh: {sorted(mesh)})"))
        elif (c["prim"] in ("all_to_all", "all_gather")
              and not set(c["axes"]) <= kvp):
            findings.append(Finding(
                check="collective.axis", path=where, symbol=symbol,
                message=f"{c['path']}: combine collective over "
                        f"{c['axes']} — the KVP combine must run over "
                        f"the KVP axes {sorted(kvp)} only"))

    for prim, want in expected.items():
        got = [c for c in colls
               if c["prim"] == prim and set(c["axes"]) & kvp]
        if len(got) != want:
            trail = [c["path"] for c in got[:3]]
            findings.append(Finding(
                check="collective.count", path=where, symbol=symbol,
                message=f"{len(got)} {prim} over KVP axes "
                        f"{sorted(kvp)}, expected {want} "
                        f"(one combine per attention layer): {trail}"))

    bad = []
    _walk_dtypes(jaxpr.jaxpr, bad)
    if bad:
        findings.append(Finding(
            check="dtype.upcast", path=where, symbol=symbol,
            message=f"fp64/complex128 values in the traced step: "
                    f"{bad[:3]}"))
    return findings


def check_state_dtypes(fn, args, state_index, where, symbol) -> list[Finding]:
    """Decode-state dtype preservation via ``jax.eval_shape``.

    ``args[state_index]`` is the state pytree the step returns updated;
    every leaf's dtype must survive the step (int8 caches stay int8).
    """
    try:
        out = jax.eval_shape(fn, *args)
    except Exception as e:
        return [Finding(check="dtype.upcast", path=where, symbol=symbol,
                        message=f"eval_shape failed: {e!r}")]
    in_state = args[state_index]
    out_state = None
    for leaf_tree in (out if isinstance(out, tuple) else (out,)):
        paths = jax.tree_util.tree_structure(leaf_tree)
        if paths == jax.tree_util.tree_structure(in_state):
            out_state = leaf_tree
            break
    if out_state is None:
        return []               # step does not return the state pytree
    bad = []
    ins = jax.tree_util.tree_leaves_with_path(in_state)
    outs = jax.tree_util.tree_leaves_with_path(out_state)
    for (p, a), (_, b) in zip(ins, outs):
        if a.dtype != b.dtype:
            bad.append((jax.tree_util.keystr(p), str(a.dtype),
                        str(b.dtype)))
    if bad:
        return [Finding(
            check="dtype.upcast", path=where, symbol=symbol,
            message=f"decode-state leaves change dtype through the step "
                    f"(silent cache upcast): {bad[:3]}")]
    return []


def _decode_expected_combines(cfg) -> int:
    """Attention sublayers per scan period == KVP combines in the jaxpr.

    ``build_serve_step`` scans over layer periods; the scan body holds
    ``p = local_ratio + 1`` sublayers (or 1 without a local/global split),
    each running one ``helix_attention`` == one all_to_all + all_gather.
    The scan body is traced once, so the jaxpr records exactly ``p``
    combines for attention archs and 0 for pure-SSM archs.
    """
    if not getattr(cfg, "has_attention", True):
        return 0
    p = (cfg.local_ratio + 1) if getattr(cfg, "local_ratio", 0) else 1
    return p


def run_jaxpr_audit(report: Report, arch: str = "granite-3-2b") -> None:
    """Trace the real serving step graphs for ``arch`` and audit them.

    Uses the reduced config on a 1x1 ("data", "model") mesh with
    ``kvp_axes=("data",)`` and ``hopb_chunks=1`` — collective equations
    are recorded inside shard_map even at axis size 1, so the HOP-B
    dataflow is checked without multi-device hardware.
    """
    import functools

    from repro.configs import get_config
    from repro.core.sharding import HelixConfig
    from repro.models.model_zoo import build_serve_step, make_prefill_step
    from repro.models.transformer import init_params
    from repro.utils import make_mesh

    cfg = get_config(arch).reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    hx = HelixConfig(kvp_axes=("data",), tpa_axis=None)
    where = "src/repro/models/decode_model.py"

    # shapes only — eval_shape keeps the audit allocation-free
    params = jax.eval_shape(functools.partial(init_params, cfg),
                            jax.random.PRNGKey(0))
    b, s_cap = 2, 32
    toks = jax.ShapeDtypeStruct((b, 8), jnp.int32)
    prefill_step = make_prefill_step(cfg, mesh, hx, s_cap=s_cap)
    _, state = jax.eval_shape(prefill_step, params, {"tokens": toks})
    cur = jax.ShapeDtypeStruct((b,), jnp.int32)

    serve_step = build_serve_step(cfg, mesh, hx, hopb_chunks=1)
    p = _decode_expected_combines(cfg)
    expected = {"all_to_all": p, "all_gather": p, "psum": 0}
    report.extend(audit_step_fn(
        serve_step, (params, state, cur),
        kvp_axes=("data",), mesh_axes=mesh.axis_names, expected=expected,
        where=where, symbol=f"build_serve_step[{arch}]"))
    report.extend(check_state_dtypes(
        serve_step, (params, state, cur), state_index=1,
        where=where, symbol=f"build_serve_step[{arch}]"))

    report.extend(audit_step_fn(
        prefill_step, (params, {"tokens": toks}),
        kvp_axes=("data",), mesh_axes=mesh.axis_names,
        expected={"all_to_all": 0, "all_gather": 0, "psum": 0},
        where="src/repro/models/model_zoo.py",
        symbol=f"make_prefill_step[{arch}]"))
    report.mark_run("jaxpr")
