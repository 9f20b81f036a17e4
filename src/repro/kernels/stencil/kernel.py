"""Tile compute for the Jacobi stencils (2D 5-point, 3D 7-point).

``ops.py`` runs these through the halo pipeline,
:func:`repro.kernels.pipeline.halo_pipeline_call`, which streams
overlapping ``(block_rows + 2, ...)`` tiles of the padded array HBM->VMEM
with ``num_stages`` buffers and writes disjoint ``block_rows`` output
chunks (see the pipeline-contract docstring there).  No path holds the
whole array in VMEM: an 8192^2 f32 grid alone is twice the chip's 128 MiB.

Inputs are pre-padded with one zero ring (``jnp.pad(a, 1)``) by the
``ops.py`` wrappers, so every tile fetch is in bounds without clamping;
the compute functions mask physical-boundary points back to the centre
value (Dirichlet copy), which makes the result independent of the pad
contents and bit-identical to ``ref.py``.

Shapes are unconstrained in interpret mode.  On a Mosaic backend the
pipeline pads the input to whole (8, 128) tiles, and the output's trailing
dims must be whole tiles (2D: ``W`` a multiple of 128 and ``block_rows``
of 8; 3D: ``H`` of 8 and ``W`` of 128).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: default pipeline chunk per DMA: 8 rows (2D, one sublane tile) / 2
#: layers (3D: 8 layers of a 256^3 f32 grid at depth 2 exceed the chip's
#: scoped VMEM; 2 layers compile up to depth 3).
BLOCK_ROWS = 8
BLOCK_LAYERS = 2


# ---------------------------------------------------------------------------
# tile compute
# ---------------------------------------------------------------------------


def five_point_block(tile, g0, *, H: int, W: int, c0: float, c1: float):
    """5-point stencil on a padded row tile.

    ``tile``: ``(n + 2, W + 2)`` slice of the padded array whose first row
    is padded row ``g0``; returns the ``(n, W)`` output rows ``g0 ..
    g0+n-1``.  ``g0`` may be traced (the pipeline's chunk offset).
    """
    n = tile.shape[0] - 2
    c = tile[1:1 + n, 1:W + 1]
    up = tile[0:n, 1:W + 1]
    dn = tile[2:2 + n, 1:W + 1]
    lf = tile[1:1 + n, 0:W]
    rt = tile[1:1 + n, 2:W + 2]
    val = c0 * c + c1 * ((up + dn) + (lf + rt))
    rows = g0 + jax.lax.broadcasted_iota(jnp.int32, (n, W), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, W), 1)
    edge = (rows == 0) | (rows == H - 1) | (cols == 0) | (cols == W - 1)
    return jnp.where(edge, c, val)


def seven_point_block(tile, g0, *, D: int, H: int, W: int,
                      c0: float, c1: float):
    """7-point stencil on a padded layer tile: ``(n + 2, H + 2, W + 2)``
    -> output layers ``g0 .. g0+n-1`` of shape ``(n, H, W)``."""
    n = tile.shape[0] - 2
    c = tile[1:1 + n, 1:H + 1, 1:W + 1]
    kd = tile[0:n, 1:H + 1, 1:W + 1]
    ku = tile[2:2 + n, 1:H + 1, 1:W + 1]
    jn_ = tile[1:1 + n, 0:H, 1:W + 1]
    js = tile[1:1 + n, 2:H + 2, 1:W + 1]
    iw = tile[1:1 + n, 1:H + 1, 0:W]
    ie = tile[1:1 + n, 1:H + 1, 2:W + 2]
    val = c0 * c + c1 * (((kd + ku) + (jn_ + js)) + (iw + ie))
    ks = g0 + jax.lax.broadcasted_iota(jnp.int32, (n, H, W), 0)
    js_i = jax.lax.broadcasted_iota(jnp.int32, (n, H, W), 1)
    is_i = jax.lax.broadcasted_iota(jnp.int32, (n, H, W), 2)
    edge = ((ks == 0) | (ks == D - 1) | (js_i == 0) | (js_i == H - 1)
            | (is_i == 0) | (is_i == W - 1))
    return jnp.where(edge, c, val)
