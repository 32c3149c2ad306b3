"""Pallas TPU kernels for the frugal-sketch hot path.

  frugal_update.py — ONE pl.pallas_call kernel family parameterized by a
                     core.program.LaneProgram in three bit-identical
                     lowerings — the (G, T) revisit grid (interpret-mode
                     workhorse), the Mosaic/TPU double-buffered-DMA path
                     (state VMEM-resident for the whole stream, items
                     streamed HBM→VMEM one tile ahead), and the Triton/GPU
                     body (full T loop per CTA).
  ops.py           — the single jit'd blocked/auto entry-point pair:
                     padding, dtype, packing, per-platform compiled-kernel
                     dispatch with roofline-autotuned blocks; and
                     frugal_update_sparse, the O(events) event round
                     (donation-aware two-phase XLA scatter, every platform).
                     (Plus ValueError stubs for the removed pre-program
                     entry points, naming the replacement.)
  ref.py           — pure-jnp lax.scan oracles for bit-exact validation.
"""

from .frugal_update import (
    frugal_program_pallas,
    frugal_program_pallas_dma,
    frugal_program_pallas_gpu,
)
from .ops import (
    block_override,
    frugal_update_auto,
    frugal_update_blocked,
    frugal_update_sparse,
    # Removed-path stubs: importable, raise ValueError on call with a
    # migration pointer (tests/test_deprecations.py pins the errors).
    frugal1u_update_blocked,
    frugal2u_update_blocked,
    frugal1u_update_auto,
    frugal2u_update_auto,
    frugal1u_update_blocked_fused,
    frugal2u_update_blocked_fused,
    frugal1u_update_auto_fused,
    frugal2u_update_auto_fused,
    frugal2u_update_blocked_fused_decay,
    frugal2u_update_auto_fused_decay,
    frugal1u_update_blocked_fused_window,
    frugal1u_update_auto_fused_window,
    frugal2u_update_blocked_fused_window,
    frugal2u_update_auto_fused_window,
)

# __all__ names only the live API: the removed-path stubs above stay
# importable for the loud ValueError, but they are no longer part of the
# public surface (repro.api.lint checks every listed name resolves).
__all__ = [
    "block_override",
    "frugal_program_pallas",
    "frugal_program_pallas_dma",
    "frugal_program_pallas_gpu",
    "frugal_update_auto",
    "frugal_update_blocked",
    "frugal_update_sparse",
]
