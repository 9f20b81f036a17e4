"""Pallas TPU kernels for the paper's streaming microbenchmarks (§V).

TPU adaptation of the paper's Table I kernel set.  The cache line (64 B)
becomes a VMEM block (``BLOCK`` elements, a multiple of the 8x128 VPU tile);
the grid streams blocks HBM -> VMEM -> VREG, processes them on the VPU and
streams results back.  Because Pallas ``out_specs`` write whole blocks, the
output stream never reads its destination: the paper's *non-temporal store*
(§VII-E) is the structural default on TPU — the write-allocate/RFO variant
is modelled by ``*_inplace`` wrappers that alias input and output
(read-modify-write), see ``ops.py``.

Scalars arrive as (1, 1) SMEM-style blocks so they stay runtime values.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..pipeline import _fit_block

#: default block: 8 sublanes x 128 lanes x 8 rows = fits VMEM comfortably and
#: keeps the MXU/VPU tile alignment (multiples of (8, 128)).
BLOCK_ROWS = 64
BLOCK_COLS = 128


def _grid(n_rows: int, block_rows: int) -> tuple[int]:
    assert n_rows % block_rows == 0, (n_rows, block_rows)
    return (n_rows // block_rows,)


def _io_spec(block_rows: int):
    return pl.BlockSpec((block_rows, BLOCK_COLS), lambda i: (i, 0))


def _scalar_spec():
    return pl.BlockSpec((1, 1), lambda i: (0, 0))


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------


def _copy_kernel(b_ref, a_ref):
    a_ref[...] = b_ref[...]


def _store_kernel(s_ref, a_ref):
    a_ref[...] = jnp.full_like(a_ref, s_ref[0, 0])


def _update_kernel(s_ref, a_in_ref, a_ref):
    a_ref[...] = s_ref[0, 0] * a_in_ref[...]


def _striad_kernel(s_ref, b_ref, c_ref, a_ref):
    a_ref[...] = b_ref[...] + s_ref[0, 0] * c_ref[...]


def _schoenauer_kernel(b_ref, c_ref, d_ref, a_ref):
    a_ref[...] = b_ref[...] + c_ref[...] * d_ref[...]


def _load_kernel(a_ref, o_ref):
    """s += A[i] — per-lane partial sums of each block, accumulated across
    the sequential grid into one (1, 128) row; the caller sums the lanes."""

    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.sum(a_ref[...].astype(o_ref.dtype), axis=0,
                          keepdims=True)


def _ddot_kernel(a_ref, b_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.sum((a_ref[...] * b_ref[...]).astype(o_ref.dtype),
                          axis=0, keepdims=True)


# ---------------------------------------------------------------------------
# pallas_call builders
# ---------------------------------------------------------------------------


def _compiler_params(semantics: str, interpret: bool):
    """Declare grid-dimension semantics to Mosaic: ``parallel`` grid steps
    may be reordered/overlapped by the pipeliner, ``arbitrary`` ones are
    sequential (reductions).  Ignored (but accepted) in interpret mode."""
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=(semantics,))


def _streaming_call(body, n_in: int, *, scalar_first: bool, interpret: bool,
                    block_rows: int, x_shape, dtype):
    rows = x_shape[0]
    block_rows = _fit_block(rows, block_rows)
    in_specs = ([_scalar_spec()] if scalar_first else []) + [
        _io_spec(block_rows) for _ in range(n_in)
    ]
    return pl.pallas_call(
        body,
        grid=_grid(rows, block_rows),
        in_specs=in_specs,
        out_specs=_io_spec(block_rows),
        out_shape=jax.ShapeDtypeStruct(x_shape, dtype),
        interpret=interpret,
        compiler_params=_compiler_params("parallel", interpret),
    )


def copy_call(x_shape, dtype, *, block_rows=BLOCK_ROWS, interpret=False):
    return _streaming_call(_copy_kernel, 1, scalar_first=False,
                           interpret=interpret, block_rows=block_rows,
                           x_shape=x_shape, dtype=dtype)


def store_call(x_shape, dtype, *, block_rows=BLOCK_ROWS, interpret=False):
    return _streaming_call(_store_kernel, 0, scalar_first=True,
                           interpret=interpret, block_rows=block_rows,
                           x_shape=x_shape, dtype=dtype)


def update_call(x_shape, dtype, *, block_rows=BLOCK_ROWS, interpret=False):
    return _streaming_call(_update_kernel, 1, scalar_first=True,
                           interpret=interpret, block_rows=block_rows,
                           x_shape=x_shape, dtype=dtype)


def striad_call(x_shape, dtype, *, block_rows=BLOCK_ROWS, interpret=False):
    return _streaming_call(_striad_kernel, 2, scalar_first=True,
                           interpret=interpret, block_rows=block_rows,
                           x_shape=x_shape, dtype=dtype)


def schoenauer_call(x_shape, dtype, *, block_rows=BLOCK_ROWS, interpret=False):
    return _streaming_call(_schoenauer_kernel, 3, scalar_first=False,
                           interpret=interpret, block_rows=block_rows,
                           x_shape=x_shape, dtype=dtype)


def _reduce_call(body, n_in, x_shape, dtype, *, block_rows, interpret):
    rows = x_shape[0]
    block_rows = _fit_block(rows, block_rows)
    acc_dtype = jnp.float32 if dtype in (jnp.bfloat16, jnp.float16) else dtype
    return pl.pallas_call(
        body,
        grid=_grid(rows, block_rows),
        in_specs=[_io_spec(block_rows) for _ in range(n_in)],
        out_specs=pl.BlockSpec((1, BLOCK_COLS), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, BLOCK_COLS), acc_dtype),
        interpret=interpret,
        compiler_params=_compiler_params("arbitrary", interpret),
    )


def load_call(x_shape, dtype, *, block_rows=BLOCK_ROWS, interpret=False):
    return _reduce_call(_load_kernel, 1, x_shape, dtype,
                        block_rows=block_rows, interpret=interpret)


def ddot_call(x_shape, dtype, *, block_rows=BLOCK_ROWS, interpret=False):
    return _reduce_call(_ddot_kernel, 2, x_shape, dtype,
                        block_rows=block_rows, interpret=interpret)
