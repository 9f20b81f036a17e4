"""Jitted public wrappers for the streaming Pallas kernels.

``interpret`` defaults to True on CPU backends (this container) so the
kernel bodies execute in Python for correctness validation; on a real TPU
backend the same code lowers to Mosaic.

Every op takes ``num_stages``: ``None`` uses the classic one-block-per-
grid-step kernels (the implicit pallas_call pipeline); an integer routes
through the shared multi-buffered DMA pipeline engine with that many VMEM
buffers per stream.  The pipeline contract — block-shape fitting,
``num_stages`` semantics (1 = serial / no overlap, 2 = double buffering,
...), bit-identity across depths, and the halo handling used by the
stencil family — is documented once, in :mod:`repro.kernels.pipeline`
where the engine lives; these wrappers only pick a compute function and
one of its builders (``map_pipeline_call`` for elementwise streams,
``reduce_pipeline_call`` for ``load``/``ddot``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import pipeline as P
from . import kernel as K


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _as2d(x):
    """Reshape a flat stream to (rows, BLOCK_COLS)."""
    n = x.shape[0] if x.ndim == 1 else x.shape[0] * x.shape[1]
    rows = n // K.BLOCK_COLS
    return x.reshape(rows, K.BLOCK_COLS)


def _scal(s, dtype):
    return jnp.asarray(s, dtype=dtype).reshape(1, 1)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "num_stages"))
def load(a, *, block_rows=K.BLOCK_ROWS, interpret=None, num_stages=None):
    interpret = _default_interpret() if interpret is None else interpret
    a2 = _as2d(a)
    if num_stages is not None:
        out = P.reduce_pipeline_call(
            lambda x: x, 1, x_shape=a2.shape, dtype=a2.dtype,
            num_stages=num_stages, block_rows=block_rows,
            interpret=interpret)(a2)
    else:
        out = K.load_call(a2.shape, a2.dtype, block_rows=block_rows,
                          interpret=interpret)(a2)
    return jnp.sum(out)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "num_stages"))
def ddot(a, b, *, block_rows=K.BLOCK_ROWS, interpret=None, num_stages=None):
    interpret = _default_interpret() if interpret is None else interpret
    a2, b2 = _as2d(a), _as2d(b)
    if num_stages is not None:
        out = P.reduce_pipeline_call(
            lambda x, y: x * y, 2, x_shape=a2.shape, dtype=a2.dtype,
            num_stages=num_stages, block_rows=block_rows,
            interpret=interpret)(a2, b2)
    else:
        out = K.ddot_call(a2.shape, a2.dtype, block_rows=block_rows,
                          interpret=interpret)(a2, b2)
    return jnp.sum(out)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "block_rows",
                                             "interpret", "num_stages"))
def store(s, shape, dtype, *, block_rows=K.BLOCK_ROWS, interpret=None,
          num_stages=None):
    interpret = _default_interpret() if interpret is None else interpret
    rows = (shape[0] * (shape[1] if len(shape) > 1 else 1)) // K.BLOCK_COLS
    if num_stages is not None:
        out = P.map_pipeline_call(
            lambda sv, *, shape: jnp.full(shape, sv, dtype), 1, 0,
            x_shape=(rows, K.BLOCK_COLS), dtype=dtype,
            num_stages=num_stages, block_rows=block_rows,
            interpret=interpret)(_scal(s, dtype))
    else:
        out = K.store_call((rows, K.BLOCK_COLS), dtype, block_rows=block_rows,
                           interpret=interpret)(_scal(s, dtype))
    return out.reshape(shape)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "num_stages"))
def update(s, a, *, block_rows=K.BLOCK_ROWS, interpret=None, num_stages=None):
    interpret = _default_interpret() if interpret is None else interpret
    a2 = _as2d(a)
    if num_stages is not None:
        out = P.map_pipeline_call(
            lambda sv, x: sv * x, 1, 1, x_shape=a2.shape, dtype=a2.dtype,
            num_stages=num_stages, block_rows=block_rows,
            interpret=interpret)(_scal(s, a2.dtype), a2)
    else:
        out = K.update_call(a2.shape, a2.dtype, block_rows=block_rows,
                            interpret=interpret)(_scal(s, a2.dtype), a2)
    return out.reshape(a.shape)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "num_stages"))
def copy(b, *, block_rows=K.BLOCK_ROWS, interpret=None, num_stages=None):
    interpret = _default_interpret() if interpret is None else interpret
    b2 = _as2d(b)
    if num_stages is not None:
        out = P.map_pipeline_call(
            lambda x: x, 0, 1, x_shape=b2.shape, dtype=b2.dtype,
            num_stages=num_stages, block_rows=block_rows,
            interpret=interpret)(b2)
    else:
        out = K.copy_call(b2.shape, b2.dtype, block_rows=block_rows,
                          interpret=interpret)(b2)
    return out.reshape(b.shape)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "num_stages"))
def striad(s, b, c, *, block_rows=K.BLOCK_ROWS, interpret=None,
           num_stages=None):
    interpret = _default_interpret() if interpret is None else interpret
    b2, c2 = _as2d(b), _as2d(c)
    if num_stages is not None:
        out = P.map_pipeline_call(
            lambda sv, x, y: x + sv * y, 1, 2, x_shape=b2.shape,
            dtype=b2.dtype, num_stages=num_stages, block_rows=block_rows,
            interpret=interpret)(_scal(s, b2.dtype), b2, c2)
    else:
        out = K.striad_call(b2.shape, b2.dtype, block_rows=block_rows,
                            interpret=interpret)(_scal(s, b2.dtype), b2, c2)
    return out.reshape(b.shape)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "num_stages"))
def schoenauer(b, c, d, *, block_rows=K.BLOCK_ROWS, interpret=None,
               num_stages=None):
    interpret = _default_interpret() if interpret is None else interpret
    b2, c2, d2 = _as2d(b), _as2d(c), _as2d(d)
    if num_stages is not None:
        out = P.map_pipeline_call(
            lambda x, y, z: x + y * z, 0, 3, x_shape=b2.shape,
            dtype=b2.dtype, num_stages=num_stages, block_rows=block_rows,
            interpret=interpret)(b2, c2, d2)
    else:
        out = K.schoenauer_call(b2.shape, b2.dtype, block_rows=block_rows,
                                interpret=interpret)(b2, c2, d2)
    return out.reshape(b.shape)


# ---------------------------------------------------------------------------
# Fused multi-kernel chains (intermediate stays in VMEM)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "num_stages"))
def triad_update(s, t, b, c, *, block_rows=K.BLOCK_ROWS, interpret=None,
                 num_stages=2):
    """Fused triad->update chain: ``A[i] = t * (B[i] + s*C[i])``.

    The triad result never round-trips through HBM: 3 streams instead of
    the 5 of ``update(t, striad(s, b, c))`` — the ECM stream count
    predicts the 5/3 memory-bound speedup (see ``pipeline.py``).
    """
    interpret = _default_interpret() if interpret is None else interpret
    b2, c2 = _as2d(b), _as2d(c)
    out = P.map_pipeline_call(
        P.fused_compute_triad_update, 2, 2, x_shape=b2.shape, dtype=b2.dtype,
        num_stages=num_stages, block_rows=block_rows, interpret=interpret,
    )(_scal(s, b2.dtype), _scal(t, b2.dtype), b2, c2)
    return out.reshape(b.shape)


def triad_update_unfused(s, t, b, c, *, block_rows=K.BLOCK_ROWS,
                         interpret=None, num_stages=2):
    """Reference chain through HBM: two kernel launches, 5 streams."""
    a = striad(s, b, c, block_rows=block_rows, interpret=interpret,
               num_stages=num_stages)
    return update(t, a, block_rows=block_rows, interpret=interpret,
                  num_stages=num_stages)


# ---------------------------------------------------------------------------
# RFO-analogue variants (§VII-E inverted): force a read-modify-write of the
# output stream by aliasing it as an input, i.e. the "regular store" case of
# the paper.  Used by the fig12 TPU benchmark to contrast traffic.
# ---------------------------------------------------------------------------


@jax.jit
def striad_rmw(s, a, b, c):
    """A[i] = B[i] + s*C[i], but reading A first (write-allocate analogue)."""
    return (a * 0 + b + s * c).astype(a.dtype)
