"""Jitted public wrappers for the Jacobi stencil Pallas kernels.

``interpret`` defaults to True on CPU backends (this container); on a real
TPU backend the same code lowers to Mosaic.

Every call runs the halo-aware multi-buffered DMA pipeline
(:func:`repro.kernels.pipeline.halo_pipeline_call`) with ``num_stages``
VMEM buffers per stream (1 = serial / no overlap, 2 = double buffering,
...); ``None`` means the default depth, :data:`DEFAULT_STAGES`.
Outputs are bit-identical across every ``num_stages`` setting and to the
``ref.py`` oracles — enforced by ``tests/test_stencil.py``.

The wrappers pad the input with one zero ring before the pallas_call so
every pipeline fetch is in bounds; the kernels mask physical-boundary
points back to the input value (Dirichlet copy), making the result
independent of the pad contents.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import pipeline as P
from . import kernel as K


#: pipeline depth for ``num_stages=None``
DEFAULT_STAGES = 2


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("c0", "c1", "num_stages",
                                             "block_rows", "interpret"))
def jacobi2d(a, *, c0: float = 0.0, c1: float = 0.25, num_stages=None,
             block_rows: int = K.BLOCK_ROWS, interpret=None):
    """2D 5-point Jacobi sweep: ``b = c0*a + c1*(N+S+W+E)`` interior,
    ``b = a`` on the boundary."""
    interpret = _default_interpret() if interpret is None else interpret
    H, W = a.shape
    p = jnp.pad(a, 1)
    compute = functools.partial(K.five_point_block, H=H, W=W, c0=c0, c1=c1)
    return P.halo_pipeline_call(
        compute, out_shape=(H, W), in_shape=p.shape, dtype=a.dtype, halo=1,
        num_stages=num_stages or DEFAULT_STAGES, block_rows=block_rows,
        interpret=interpret,
    )(p)


@functools.partial(jax.jit, static_argnames=("c0", "c1", "num_stages",
                                             "block_rows", "interpret"))
def jacobi3d(a, *, c0: float = 0.0, c1: float = 1.0 / 6.0, num_stages=None,
             block_rows: int = K.BLOCK_LAYERS, interpret=None):
    """3D 7-point Jacobi sweep over (D, H, W); the pipeline chunks along
    the outermost (layer) axis with a one-layer halo."""
    interpret = _default_interpret() if interpret is None else interpret
    D, H, W = a.shape
    p = jnp.pad(a, 1)
    compute = functools.partial(K.seven_point_block, D=D, H=H, W=W,
                                c0=c0, c1=c1)
    return P.halo_pipeline_call(
        compute, out_shape=(D, H, W), in_shape=p.shape, dtype=a.dtype,
        halo=1, num_stages=num_stages or DEFAULT_STAGES, block_rows=block_rows,
        interpret=interpret,
    )(p)
