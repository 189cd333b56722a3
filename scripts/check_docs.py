#!/usr/bin/env python
"""CI docs check (scripts/ci.sh): fails when

1. a public symbol of a kernel family's ``ops.py`` (or a listed public-API
   entry point) lacks a docstring, or
2. a ``--flag`` shown in a README.md code block for one of the repo's CLIs
   doesn't exist in that CLI's argparse any more (README drift).

Run directly:  PYTHONPATH=src python scripts/check_docs.py
"""
from __future__ import annotations

import importlib
import inspect
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

ERRORS: list[str] = []


def err(msg: str) -> None:
    ERRORS.append(msg)


# ------------------------------------------------------------- docstrings
def _check_doc(qualname: str, obj) -> None:
    doc = inspect.getdoc(obj)
    if not doc or not doc.strip():
        err(f"missing docstring: {qualname}")


def check_docstrings() -> None:
    from repro.kernels import registry

    # every kernel family's ops.py public surface
    for fam in registry.FAMILIES.values():
        mod_name = fam.kernel.split(":")[0]
        mod = importlib.import_module(mod_name)
        _check_doc(mod_name, mod)
        for name, obj in vars(mod).items():
            if name.startswith("_") or not callable(obj):
                continue
            if getattr(obj, "__wrapped__", None) is not None:
                obj = obj.__wrapped__          # unwrap functools/jax.jit
            if getattr(obj, "__module__", mod_name) != mod_name:
                continue                       # re-exports checked at home
            _check_doc(f"{mod_name}.{name}", obj)

    # the documented public API entry points
    public = [
        ("repro.core.sharding", "HelixConfig"),
        ("repro.core.helix", "helix_attention"),
        ("repro.core.helix", "append_kv"),
        ("repro.core.helix", "fuse_append_applicable"),
        ("repro.models.decode_model", "build_serve_step"),
        ("repro.models.decode_model", "build_serve_multistep"),
        ("repro.models.model_zoo", "make_train_step"),
        ("repro.models.model_zoo", "make_prefill_step"),
        ("repro.models.model_zoo", "make_chunk_prefill_step"),
        ("repro.models.model_zoo", "init_prefill_buffers"),
        ("repro.models.model_zoo", "finalize_chunked_prefill"),
        ("repro.models.decode_model", "prepare_decode_params"),
        ("repro.models.attention", "prefill_attention"),
        ("repro.models.attention", "decode_attention"),
        ("repro.serving.engine", "DecodeEngine"),
        ("repro.serving.scheduler", "Scheduler"),
        ("repro.serving.scheduler", "Request"),
        ("repro.serving.scheduler", "PrefixIndex"),
        ("repro.serving.scheduler", "TenantConfig"),
        ("repro.serving.metrics", "EngineMetrics"),
        ("repro.serving.sampling", "SamplingParams"),
        ("repro.serving.sampling", "sample_tokens"),
        ("repro.serving.sampling", "sample_oracle"),
        ("repro.serving.sampling", "request_seed"),
        ("repro.serving.sampling", "gumbel_noise"),
        ("repro.serving.metrics", "VirtualClock"),
        ("repro.serving.governor", "TTLGovernor"),
        ("repro.serving.governor", "GovernorConfig"),
        ("repro.serving.workload", "TraceRow"),
        ("repro.serving.workload", "TenantSpec"),
        ("repro.serving.workload", "parse_tenants"),
        ("repro.serving.workload", "generate_trace"),
        ("repro.serving.workload", "poisson_arrival_steps"),
        ("repro.serving.workload", "bursty_arrival_steps"),
        ("repro.serving.workload", "save_trace"),
        ("repro.serving.workload", "load_trace"),
        ("repro.serving.workload", "trace_id"),
        ("repro.serving.workload", "prompt_tokens"),
        ("repro.serving.workload", "requests_from_trace"),
        ("repro.serving.pool", "BlockAllocator"),
        ("repro.serving.pool", "pages_for"),
        ("repro.serving.tier", "HostPageStore"),
        ("repro.serving.faults", "FaultPlan"),
        ("repro.serving.faults", "FaultInjector"),
        ("repro.core.kvcache", "quantize_decode_state"),
        ("repro.core.kvcache", "cache_to_pages"),
        ("repro.core.kvcache", "pages_to_cache"),
        ("repro.core.kvcache", "gather_pages"),
        ("repro.core.kvcache", "state_to_paged"),
        ("repro.core.kvcache", "page_positions"),
        ("repro.core.kvcache", "gather_pool_pages"),
        ("repro.core.kvcache", "scatter_pool_pages"),
        ("repro.core.helix", "paged_slot_of_position"),
        ("repro.kernels.pruning", "span_clamp"),
        ("repro.kernels.registry", "KernelFamily"),
        ("repro.kernels.registry", "backend_table"),
        ("repro.kernels.registry", "contract_suite"),
        ("repro.kernels.contract", "KernelContract"),
        ("repro.kernels.contract", "Operand"),
        ("repro.analysis.findings", "Finding"),
        ("repro.analysis.findings", "Report"),
        ("repro.analysis.findings", "load_baseline"),
        ("repro.analysis.index_audit", "audit_contract"),
        ("repro.analysis.index_audit", "run_index_audit"),
        ("repro.analysis.index_audit", "eval_index_table"),
        ("repro.analysis.jaxpr_audit", "audit_step_fn"),
        ("repro.analysis.jaxpr_audit", "collect_collectives"),
        ("repro.analysis.jaxpr_audit", "run_jaxpr_audit"),
        ("repro.analysis.host_sync", "lint_source"),
        ("repro.analysis.host_sync", "lint_paths"),
    ]
    for mod_name, sym in public:
        mod = importlib.import_module(mod_name)
        obj = getattr(mod, sym, None)
        if obj is None:
            err(f"public symbol vanished: {mod_name}.{sym}")
            continue
        _check_doc(f"{mod_name}.{sym}", obj)
        if inspect.isclass(obj):
            for mname, meth in vars(obj).items():
                if not mname.startswith("_") and callable(meth):
                    _check_doc(f"{mod_name}.{sym}.{mname}", meth)


# ------------------------------------------------------------ README drift
# CLI target -> source file whose argparse defines its flags
CLI_SOURCES = {
    "repro.launch.serve": ROOT / "src/repro/launch/serve.py",
    "repro.launch.train": ROOT / "src/repro/launch/train.py",
    "bench_decode_kernel.py": ROOT / "benchmarks/bench_decode_kernel.py",
    "bench_serving.py": ROOT / "benchmarks/bench_serving.py",
    "analyze.py": ROOT / "scripts/analyze.py",
}
FLAG_RE = re.compile(r"add_argument\(\s*[\"'](--[A-Za-z0-9-]+)[\"']")


def _argparse_flags(path: pathlib.Path) -> set[str]:
    return set(FLAG_RE.findall(path.read_text()))


def check_readme_flags() -> None:
    readme = (ROOT / "README.md").read_text()
    blocks = re.findall(r"```(?:bash|sh|shell)?\n(.*?)```", readme, re.S)
    for block in blocks:
        targets = [t for t in CLI_SOURCES if t in block]
        if not targets:
            continue
        known = set().union(*(_argparse_flags(CLI_SOURCES[t])
                              for t in targets))
        used = set(re.findall(r"(--[A-Za-z0-9][A-Za-z0-9-]*)", block))
        for flag in sorted(used - known):
            err(f"README flag {flag} not found in argparse of "
                f"{' / '.join(targets)} (drifted?)")


def main() -> int:
    check_docstrings()
    check_readme_flags()
    if ERRORS:
        print("[check_docs] FAILED:")
        for e in ERRORS:
            print(f"  - {e}")
        return 1
    print("[check_docs] OK (docstrings + README flags)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
