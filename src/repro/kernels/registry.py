"""Unified kernel-backend registry: one switchboard for every kernel family.

Every compute hotspot in this repo ships as a *family* of interchangeable
implementations of one contract:

  * ``ref``              — the pure-jnp oracle (always available, defines the
                           semantics; also the gradient path).
  * ``pallas-interpret`` — the Pallas TPU kernel executed by the Pallas
                           interpreter.  Runs on any JAX backend (CPU CI),
                           proves the kernel's *semantics*, not its speed.
  * ``pallas``           — the same kernel compiled for real TPU hardware.

The four registered families (see ``FAMILIES``):

  ============== ============================== ==============================
  family         used by                        oracle
  ============== ============================== ==============================
  flash_decode   Helix decode attention         kernels/flash_decode/ref.py
                 (core/helix.py::_local_attend)
  flash_prefill  full-sequence attention        kernels/flash_prefill/ref.py
                 (models/attention.py prefill)
  ssd_prefill    Mamba2 SSD scan core           kernels/ssd_prefill/ref.py
                 (models/ssm.py::ssd_chunked)
  w8a16_matmul   int8-weight matmul             kernels/w8a16_matmul/ref.py
                 (weight-quantized projections)
  ============== ============================== ==============================

Selection is per-family via ``HelixConfig`` (core/sharding.py):
``attn_backend`` (flash_decode), ``prefill_backend`` (flash_prefill),
``ssd_backend`` (ssd_prefill), ``matmul_backend`` (w8a16_matmul); a field
left unset takes ``default_backend`` (compiled Pallas on a TPU, ``ref``
elsewhere) — plumbed
through ``build_serve_step`` / ``make_prefill_step`` / ``make_train_step``,
``launch/serve.py`` / ``launch/train.py`` CLI flags and the serving engine.

This module is intentionally free of model imports (kernels are the bottom
layer); call sites ask the registry to *validate* and *describe* backends and
to map a backend string to the ``interpret`` flag of the family's Pallas op.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax

BACKENDS = ("ref", "pallas-interpret", "pallas")

# HelixConfig field name -> kernel family routed by it.
FAMILY_FIELDS = {
    "attn_backend": "flash_decode",
    "prefill_backend": "flash_prefill",
    "ssd_backend": "ssd_prefill",
    "matmul_backend": "w8a16_matmul",
}


@dataclasses.dataclass(frozen=True)
class KernelFamily:
    """One kernel family: a contract with a ref oracle and a Pallas kernel.

    ``ref`` / ``kernel`` are import paths resolved lazily (the registry must
    import before any kernel module so families can self-describe without
    cycles).  ``grad`` records how gradients flow through the Pallas path:
    ``"ref-vjp"`` = custom_vjp whose backward re-runs the oracle;
    ``"none"`` = forward-only (decode has no backward pass).

    ``accounting`` (optional) names the family's block/bytes accounting
    function — host-side arithmetic that replays the kernel's pruning
    ``index_map`` and reports the HBM blocks/bytes a call streams
    (benchmarks and the CI pruning smoke consume it via
    ``registry.accounting``).

    ``contract`` names the family's static-analysis contract hook: a
    zero-argument function returning the ``KernelContract`` list
    (``kernels/contract.py``) the index-space auditor (``repro.analysis``)
    proves bounds/DMA-elision/alias-race properties over.  Every family
    must carry one — ``scripts/analyze.py --strict`` fails loudly
    (``contract.missing``) for a family without it rather than silently
    skipping it.
    """
    name: str
    ref: str                  # "module:function" of the pure-jnp oracle
    kernel: str               # "module:function" of the Pallas op wrapper
    used_by: str              # call-site summary for the backend table
    grad: str = "none"        # "none" | "ref-vjp"
    accounting: str | None = None   # "module:function" block accounting
    contract: str | None = None     # "module:function" analysis contracts

    def _load(self, spec: str) -> Callable:
        import importlib
        mod, fn = spec.split(":")
        return getattr(importlib.import_module(mod), fn)

    def resolve(self, backend: str) -> Callable:
        """Return the family's callable for ``backend``.

        ``ref`` returns the oracle; the Pallas backends return the op wrapper
        (call it with ``interpret=interpret_flag(backend)``).  Call sites that
        need backend-specific argument mapping keep doing it themselves — the
        registry's job is routing and validation, not signature unification.
        """
        validate(self.name, backend)
        return self._load(self.ref if backend == "ref" else self.kernel)


FAMILIES: dict[str, KernelFamily] = {
    f.name: f for f in (
        KernelFamily(
            name="flash_decode",
            ref="repro.kernels.flash_decode.ref:flash_decode_ref",
            kernel="repro.kernels.flash_decode.ops:flash_decode",
            used_by="Helix decode attention (core/helix._local_attend)",
            grad="none",
            accounting="repro.kernels.flash_decode.ops:"
                       "flash_decode_accounting",
            contract="repro.kernels.flash_decode.ops:"
                     "flash_decode_contract"),
        KernelFamily(
            name="flash_prefill",
            ref="repro.kernels.flash_prefill.ref:flash_prefill_ref",
            kernel="repro.kernels.flash_prefill.ops:flash_prefill",
            used_by="prefill/train attention (models/attention."
                    "prefill_attention)",
            grad="ref-vjp",
            accounting="repro.kernels.flash_prefill.ops:"
                       "flash_prefill_accounting",
            contract="repro.kernels.flash_prefill.ops:"
                     "flash_prefill_contract"),
        KernelFamily(
            name="ssd_prefill",
            ref="repro.kernels.ssd_prefill.ref:ssd_prefill_ref",
            kernel="repro.kernels.ssd_prefill.ops:ssd_prefill",
            used_by="Mamba2 SSD prefill core (models/ssm.ssd_chunked)",
            grad="ref-vjp",
            contract="repro.kernels.ssd_prefill.ops:ssd_prefill_contract"),
        KernelFamily(
            name="w8a16_matmul",
            ref="repro.kernels.w8a16_matmul.ref:w8a16_matmul_ref",
            kernel="repro.kernels.w8a16_matmul.ops:w8a16_matmul",
            used_by="int8-weight lm_head matmul (decode_model, "
                    "HelixConfig.lm_head_w8); its logits feed the fused "
                    "on-device sampling epilogue (serving/sampling.py)",
            grad="none",
            contract="repro.kernels.w8a16_matmul.ops:"
                     "w8a16_matmul_contract"),
    )
}


def validate(family: str, backend: str) -> str:
    """Assert ``family``/``backend`` are registered; returns ``backend``."""
    if family not in FAMILIES:
        raise ValueError(f"unknown kernel family {family!r}; "
                         f"registered: {sorted(FAMILIES)}")
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r} for family "
                         f"{family!r}; choose from {BACKENDS}")
    return backend


def resolve(family: str, backend: str) -> Callable:
    """Shorthand for ``FAMILIES[family].resolve(backend)``."""
    if family not in FAMILIES:
        raise ValueError(f"unknown kernel family {family!r}; "
                         f"registered: {sorted(FAMILIES)}")
    return FAMILIES[family].resolve(backend)


def accounting(family: str) -> Callable:
    """The family's block/bytes accounting function (see ``KernelFamily``).

    Raises ``ValueError`` for unknown families and families without an
    accounting layer (only the pruning attention kernels carry one).
    """
    if family not in FAMILIES:
        raise ValueError(f"unknown kernel family {family!r}; "
                         f"registered: {sorted(FAMILIES)}")
    fam = FAMILIES[family]
    if fam.accounting is None:
        raise ValueError(f"kernel family {family!r} has no accounting layer")
    return fam._load(fam.accounting)


def contract_suite(family: str) -> list:
    """The family's ``KernelContract`` list for the static auditor.

    Loads and calls the family's ``contract`` hook (see ``KernelFamily``).
    Raises ``ValueError`` for unknown families and for families without a
    contract hook — the analyzer turns the latter into a
    ``contract.missing`` finding instead of silently skipping the family.
    """
    if family not in FAMILIES:
        raise ValueError(f"unknown kernel family {family!r}; "
                         f"registered: {sorted(FAMILIES)}")
    fam = FAMILIES[family]
    if fam.contract is None:
        raise ValueError(f"kernel family {family!r} has no analysis "
                         f"contract hook (see docs/analysis.md)")
    return fam._load(fam.contract)()


def interpret_flag(backend: str) -> bool:
    """The ``interpret=`` value for a Pallas backend string."""
    assert backend in ("pallas-interpret", "pallas"), backend
    return backend != "pallas"


def uses_kernel(backend: str) -> bool:
    """True when ``backend`` routes to the Pallas kernel (either mode)."""
    return backend in ("pallas-interpret", "pallas")


def default_backend(family: str) -> str:
    """The backend a family runs when nothing selects one: the compiled
    Pallas kernel on a TPU, the ``ref`` oracle elsewhere (CPU tests keep
    their meaning, and the interpreter is only ever chosen explicitly).
    ``HelixConfig`` resolves its unset ``*_backend`` fields through here,
    so this is the one place the platform picks kernels."""
    validate(family, "ref")
    return "pallas" if jax.devices()[0].platform == "tpu" else "ref"


def available(family: str, backend: str) -> tuple[bool, str]:
    """(is_available_here, reason).  ``pallas`` needs a real TPU device;
    ``ref`` and ``pallas-interpret`` run on every JAX backend."""
    validate(family, backend)
    if backend != "pallas":
        return True, "any backend"
    plat = jax.devices()[0].platform
    if plat == "tpu":
        return True, "tpu detected"
    return False, f"needs TPU (this host: {plat})"


def backend_table() -> str:
    """Human-readable per-family backend availability matrix.

    Printed by ``launch/serve.py --list-backends`` and doubles as a CI smoke
    target (scripts/ci.sh) — it imports every registered family lazily, so a
    broken kernel module fails the listing.
    """
    rows = [f"{'family':<14s} {'grad':<8s} "
            + "".join(f"{b:<18s}" for b in BACKENDS)
            + f"{'contract':<10s}" + "  used by"]
    rows.append("-" * 88)
    for name, fam in FAMILIES.items():
        cells = []
        for b in BACKENDS:
            ok, why = available(name, b)
            cells.append("yes" if ok else f"no: {why.split(' (')[0]}")
        for backend in ("ref", "pallas-interpret"):
            # resolving imports the module: a broken kernel fails loudly here
            fam.resolve(backend)
        if fam.contract is not None:
            # same loud-failure policy for the analysis contract hook
            fam._load(fam.contract)
            contract_cell = "yes"
        else:
            contract_cell = "MISSING"
        rows.append(f"{name:<14s} {fam.grad:<8s} "
                    + "".join(f"{c:<18s}" for c in cells)
                    + f"{contract_cell:<10s}" + f"  {fam.used_by}")
    return "\n".join(rows)
