"""The fused-primitive kernel registry — one table, three consumers.

Every PUBLIC Pallas kernel in ``ops/`` declares itself here with its
XLA fallback, its parity-test anchor, its ``devtime.scope`` name, and
the gap-report scopes it closes. The table is the contract that keeps
the kernel library honest:

- ``tools/lint_instrumentation.py`` **rule 9** parses this dict
  literal (AST, never imports the package) and enforces both
  directions: every public kernel function in ``ops/`` that reaches a
  ``pl.pallas_call`` has an entry (with a resolvable fallback, an
  existing parity test, and a scope site listed in ``SCOPE_SITES``),
  and every entry names a live kernel — plus the blanket rule that
  ``pl.pallas_call`` appears nowhere outside ``ops/``.
- ``obs/devtime.py`` ``gap_report()`` consults :func:`closed_by`:
  a ``pallas_candidate`` scope whose pattern a registered (and
  gate-active) kernel covers is reported CLOSED — the
  ``dl4j_tpu_devtime_scope_pallas_candidate`` gauge drops to 0 for it
  and the dossier's ``hot_path_gaps`` prints the closed/open split.
- ``tools/perf_dossier.py`` / ``bench.py`` iterate the table for the
  per-kernel parity/timing rows (``fused_epilogues`` /
  ``fused_kernels``).

``closes`` patterns are ``fnmatch`` globs over gap-report scope names.
Closure semantics: the scope's DOMINANT primitive (its attention or
normalisation math) now dispatches to the named kernel whenever the
kernel's platform gate is active — device time still reported under
the scope is the non-kernel remainder (projections, residual matmuls),
which is exactly what the dossier's closed/open split surfaces.
"""
from __future__ import annotations

import fnmatch
from typing import Any, Dict, Optional

#: kernel name -> declaration. PURE dict literal — lint rule 9 and the
#: dossier read it via AST without importing jax.
KERNEL_REGISTRY: Dict[str, Dict[str, Any]] = {
    "flash_attention": {
        "module": "ops/pallas_kernels.py",
        "fallback": "_reference_scan",
        "parity": "tests/test_pallas.py::test_flash_matches_reference",
        "scope": "ops.flash_attention",
        "closes": ("*.MultiHeadAttention", "*.SelfAttentionLayer",
                   "*.TransformerEncoderBlock",
                   "*.TransformerDecoderBlock",
                   "prefill.block_*.mixer"),
        "gate": "flash",
    },
    "flash_block_fwd": {
        "module": "ops/pallas_kernels.py",
        "fallback": "_reference_scan",
        "parity": "tests/test_pallas.py::test_flash_block_offsets_compose",
        "scope": "ops.flash_block_fwd",
        "closes": (),          # ring composition surface — the ring
        "gate": "flash",       # callers own the end-to-end scopes
    },
    "flash_block_bwd": {
        "module": "ops/pallas_kernels.py",
        "fallback": "_reference_bwd_block",
        "parity": "tests/test_pallas.py::test_flash_block_bwd_composes",
        "scope": "ops.flash_block_bwd",
        "closes": (),
        "gate": "flash",
    },
    "paged_decode_attention": {
        "module": "ops/pallas_kernels.py",
        "fallback": "_reference_paged_attention",
        "parity":
            "tests/test_pallas.py::test_paged_decode_matches_reference",
        "scope": "ops.paged_decode_attention",
        # the serving decode step's blocks: their attention now reads
        # the KV pages in place (the projections stay XLA; the MLP is
        # the block's other half, ``.ffn``, and stays an open scope)
        "closes": ("paged_decode.block_*.mixer",),
        "gate": "paged_decode",
    },
    "latent_decode_attention": {
        "module": "ops/pallas_kernels.py",
        "fallback": "_reference_latent_attention",
        "parity":
            "tests/test_pallas.py::test_latent_decode_matches_reference",
        "scope": "ops.latent_decode_attention",
        # a latent model's decode blocks share the softmax model's
        # scope names; paged_decode_attention already claims them
        "closes": (),
        "gate": "latent_decode",
    },
    "retention_decode": {
        "module": "ops/pallas_kernels.py",
        "fallback": "_reference_retention_decode",
        "parity":
            "tests/test_pallas.py::test_retention_decode_matches_reference",
        "scope": "ops.retention_decode",
        # the retention model's decode blocks: their mixer streams the
        # state pages through VMEM (the projections stay XLA; the MLP
        # is the block's other half, ``.ffn``, and stays an open scope).
        # Items are the live (slot, head) pairs, two a group, a state
        # ONE copy each way, two groups of buffers read into and two
        # written back from; reads and write-backs take turns; an
        # inactive slot costs a compare
        "closes": ("retention_decode.block_*.mixer",),
        "gate": "retention_decode",
    },
    "ssm_decode": {
        "module": "ops/pallas_kernels.py",
        "fallback": "_reference_ssm_decode",
        "parity":
            "tests/test_ssm.py::test_ssm_decode_matches_reference",
        "scope": "ops.ssm_decode",
        # a hybrid decoder's decode blocks share the softmax model's
        # scope names; paged_decode_attention already claims them.
        # Items are the live slots, eight a group, a state eight column
        # parts a copy each, two groups of buffers updated in place;
        # reads and write-backs take turns; an inactive slot costs a
        # compare
        "closes": (),
        "gate": "ssm_decode",
    },
    "experts": {
        "module": "ops/moe.py",
        "fallback": "_experts_loop",
        "parity":
            "tests/test_window_moe.py::test_expert_kernel_matches_the_loop_and_the_plain_form",
        "scope": "ops.moe_experts",
        # the expert layer's own scope inside a block's ``.ffn``; an
        # expert too large to lie in VMEM twice keeps the loop under
        # the same scope (the shape fallback underneath the gate)
        "closes": ("ops.moe_experts",),
        "gate": "moe_experts",
    },
    "threshold_encode": {
        "module": "ops/pallas_kernels.py",
        "fallback": "_jnp_threshold_encode",
        "parity": "tests/test_pallas.py::test_threshold_codec_roundtrip",
        "scope": "ops.threshold_encode",
        "closes": (),          # wire codec, not a layer epilogue
        "gate": "always",
    },
    "threshold_decode": {
        "module": "ops/pallas_kernels.py",
        "fallback": "_jnp_threshold_decode",
        "parity": "tests/test_pallas.py::test_threshold_codec_roundtrip",
        "scope": "ops.threshold_decode",
        "closes": (),
        "gate": "always",
    },
    "rms_norm": {
        "module": "ops/fused_norms.py",
        "fallback": "rms_norm_reference",
        "parity": "tests/test_fused_kernels.py::test_rms_norm_parity",
        "scope": "ops.rms_norm",
        # ONLY the scopes whose dominant primitive is the norm — the
        # decode/prefill block scopes also dispatch this kernel but
        # are matmul-dominated, and claiming them closed would hide
        # their remaining (real) pallas candidates forever
        "closes": ("*.RMSNorm",),
        "gate": "fused_norm",
    },
    "add_rms_norm": {
        "module": "ops/fused_norms.py",
        "fallback": "add_rms_norm_reference",
        "parity": "tests/test_fused_kernels.py::test_add_rms_norm_parity",
        "scope": "ops.add_rms_norm",
        "closes": (),          # rides inside *.TransformerDecoderBlock
        "gate": "fused_norm",  # (flash_attention already claims it)
    },
    "layer_norm": {
        "module": "ops/fused_norms.py",
        "fallback": "layer_norm_reference",
        "parity": "tests/test_fused_kernels.py::test_layer_norm_parity",
        "scope": "ops.layer_norm",
        "closes": ("*.LayerNormalization",),
        "gate": "fused_norm",
    },
}


def gate_active(gate: str) -> bool:
    """Is a kernel's dispatch gate live in the CURRENT environment?
    The per-shape thresholds (``DL4J_TPU_FLASH_MIN_T``,
    ``DL4J_TPU_FUSED_NORM_MIN_F``) are deliberately not modeled —
    closure is a platform-level statement ("this scope's primitive has
    a kernel and the platform dispatches it"), shape fallbacks keep
    working underneath it."""
    import jax

    from deeplearning4j_tpu.environment import get_flag
    if get_flag("DL4J_TPU_KERNEL_FORCE"):
        return True
    if gate == "always":
        return True
    return jax.default_backend() == "tpu"


def closed_by(scope: str) -> Optional[str]:
    """The registered kernel whose gate is active and whose ``closes``
    patterns cover ``scope`` — None when the gap is still open. The
    ``gap_report()`` consumer: a closed scope stops being a
    ``pallas_candidate`` and the dossier lists it under ``closed``."""
    for name, entry in KERNEL_REGISTRY.items():
        if any(fnmatch.fnmatchcase(scope, pat)
               for pat in entry["closes"]):
            if gate_active(entry["gate"]):
                return name
    return None
