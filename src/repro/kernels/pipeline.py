"""Multi-buffered HBM->VMEM DMA pipeline (the ECM overlap engine).

This module is the *shared* pipeline engine for every kernel family —
stream ops, fused chains and the halo-carrying stencils all route through
it; their ``ops.py`` wrappers only choose a compute function and a
builder.  The ECM model's central claim (Eq. 1) is
``T = max(T_nOL + T_data, T_OL)``: in-core work can hide data transfers
when the hardware overlaps them.  The default one-block-per-grid-step
Pallas kernels leave that overlap to the implicit two-deep pallas_call
pipeline; this module makes it *explicit and tunable*: inputs and outputs
live in HBM (``memory_space=ANY``) and the kernel itself runs an
``emit_pipeline``-style software pipeline with ``num_stages`` VMEM
buffers per stream and per-slot DMA semaphores:

    warm-up:  start DMAs for chunks 0..num_stages-2
    steady:   start chunk ``i+num_stages-1`` | wait chunk ``i`` | compute |
              start the output DMA for chunk ``i``
    drain:    wait the last in-flight output DMAs

The pipeline contract, common to all three builders:

* **Block shapes.**  Work is chunked along axis 0.  The requested
  ``block_rows`` is shrunk by :func:`_fit_block` to the largest divisor of
  the array's rows, so odd/prime sizes stay exact; ``n_chunks = rows //
  block_rows``.  Streaming kernels use flat ``(rows, 128)`` layouts;
  :func:`halo_pipeline_call` accepts arbitrary trailing dims (2D/3D
  stencil tiles).
* **``num_stages`` semantics.**  VMEM buffers per stream = pipeline
  depth, capped at ``n_chunks``.  ``1`` is a fully serial
  fetch->compute->store loop (the *no-overlap* bound, T_nOL + T_data);
  ``>= 2`` overlaps the next chunk's HBM reads and the previous chunk's
  write-back with compute (the *full-overlap* bound, max(T_data, T_OL)).
  Depth is a pure performance knob: outputs are bit-identical across
  ``num_stages`` (reductions accumulate in chunk order regardless of
  depth) — enforced by ``tests/test_pipeline.py`` and
  ``tests/test_stencil.py``.
* **Halo handling.**  Stencil chunks need ``halo`` extra rows on both
  sides.  :func:`halo_pipeline_call` takes a *pre-padded* input (axis 0
  length ``rows + 2*halo``; the wrapper pads, so every chunk's fetch
  window ``[c*block_rows, c*block_rows + block_rows + 2*halo)`` is in
  bounds without clamping) and fetches overlapping windows while writing
  disjoint ``block_rows``-sized outputs.  Windows and trailing dims are
  rounded up to whole (8, 128) tiles, which Mosaic's DMA requires.  The
  compute callback receives the fetched tile plus the chunk's global row
  offset so it can mask physical-boundary rows.

Measuring one kernel at ``num_stages=1`` and ``>=2`` and placing the
runtime between the two bounds yields the machine's overlap coefficient —
see ``repro.core.tpu_ecm.overlap_coefficient``.

Everything here runs bit-identically under ``interpret=True`` (CPU) and
lowers to Mosaic DMA on a real TPU backend.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


@dataclass(frozen=True)
class PipelineConfig:
    """Knobs of the software pipeline.

    ``num_stages``: VMEM buffers per stream (pipeline depth).  1 = serial
    (no overlap), 2 = double buffering, 3 = triple buffering.
    ``block_rows``: rows of 128 lanes per chunk; shrunk to the largest
    divisor of the array's rows so odd sizes stay exact.
    """

    num_stages: int = 2
    block_rows: int = 64

    def vmem_bytes(self, n_streams: int, elem_bytes: int = 4) -> int:
        return (self.num_stages * n_streams
                * self.block_rows * LANES * elem_bytes)


def _fit_block(n_rows: int, block_rows: int) -> int:
    """Largest divisor of ``n_rows`` that is <= the requested block."""
    b = max(1, min(block_rows, n_rows))
    while n_rows % b:
        b -= 1
    return b


# ---------------------------------------------------------------------------
# kernel builders
# ---------------------------------------------------------------------------


def _map_pipeline_kernel(compute, n_scalars: int, n_in: int, *,
                         n_chunks: int, stages: int, block_rows: int,
                         dtype):
    """Elementwise-map pipeline: out[chunk] = compute(*scalars, *blocks)."""

    def kernel(*refs):
        scalar_refs = refs[:n_scalars]
        in_refs = refs[n_scalars:n_scalars + n_in]
        out_ref = refs[n_scalars + n_in]

        def body(in_scr, out_scr, in_sem, out_sem):
            def in_dma(slot, chunk, j):
                return pltpu.make_async_copy(
                    in_refs[j].at[pl.ds(chunk * block_rows, block_rows), :],
                    in_scr.at[j, slot],
                    in_sem.at[j, slot],
                )

            def out_dma(slot, chunk):
                return pltpu.make_async_copy(
                    out_scr.at[slot],
                    out_ref.at[pl.ds(chunk * block_rows, block_rows), :],
                    out_sem.at[slot],
                )

            for k in range(stages - 1):                      # warm-up
                for j in range(n_in):
                    in_dma(k, k, j).start()

            def loop(chunk, _):
                slot = jax.lax.rem(chunk, stages)
                ahead = chunk + stages - 1

                @pl.when(ahead < n_chunks)
                def _():
                    for j in range(n_in):
                        in_dma(jax.lax.rem(ahead, stages), ahead, j).start()

                for j in range(n_in):
                    in_dma(slot, chunk, j).wait()

                # slot's previous output DMA must land before we overwrite
                @pl.when(chunk >= stages)
                def _():
                    out_dma(slot, chunk - stages).wait()

                scalars = [r[0, 0] for r in scalar_refs]
                if n_in:
                    blocks = [in_scr[j, slot] for j in range(n_in)]
                    val = compute(*scalars, *blocks)
                else:       # generator kernels (store): no input streams
                    val = compute(*scalars, shape=(block_rows, LANES))
                out_scr[slot] = val.astype(dtype)
                out_dma(slot, chunk).start()
                return ()

            jax.lax.fori_loop(0, n_chunks, loop, ())

            for k in range(min(stages, n_chunks)):           # drain
                chunk = n_chunks - 1 - k
                out_dma(chunk % stages, chunk).wait()

        scratch = dict(
            in_scr=pltpu.VMEM((max(n_in, 1), stages, block_rows, LANES),
                              dtype),
            out_scr=pltpu.VMEM((stages, block_rows, LANES), dtype),
            in_sem=pltpu.SemaphoreType.DMA((max(n_in, 1), stages)),
            out_sem=pltpu.SemaphoreType.DMA((stages,)),
        )
        pl.run_scoped(body, **scratch)

    return kernel


def _reduce_pipeline_kernel(compute, n_in: int, *, n_chunks: int,
                            stages: int, block_rows: int, dtype, acc_dtype):
    """Reduction pipeline: ``out[0, l] = sum over chunks and rows of
    compute(*blocks)[:, l]`` — one partial sum per lane, which the caller
    reduces to a scalar (Mosaic stores vectors, not scalars, to VMEM).

    The accumulation order is chunk-sequential and independent of
    ``num_stages``, so results are bit-identical across pipeline depths.
    """

    def kernel(*refs):
        in_refs = refs[:n_in]
        out_ref = refs[n_in]

        def body(in_scr, in_sem):
            def in_dma(slot, chunk, j):
                return pltpu.make_async_copy(
                    in_refs[j].at[pl.ds(chunk * block_rows, block_rows), :],
                    in_scr.at[j, slot],
                    in_sem.at[j, slot],
                )

            for k in range(stages - 1):
                for j in range(n_in):
                    in_dma(k, k, j).start()

            def loop(chunk, acc):
                slot = jax.lax.rem(chunk, stages)
                ahead = chunk + stages - 1

                @pl.when(ahead < n_chunks)
                def _():
                    for j in range(n_in):
                        in_dma(jax.lax.rem(ahead, stages), ahead, j).start()

                for j in range(n_in):
                    in_dma(slot, chunk, j).wait()

                blocks = [in_scr[j, slot] for j in range(n_in)]
                return acc + jnp.sum(compute(*blocks).astype(acc_dtype),
                                     axis=0, keepdims=True)

            acc0 = jnp.zeros((1, LANES), acc_dtype)
            out_ref[...] = jax.lax.fori_loop(0, n_chunks, loop, acc0)

        pl.run_scoped(
            body,
            in_scr=pltpu.VMEM((n_in, stages, block_rows, LANES), dtype),
            in_sem=pltpu.SemaphoreType.DMA((n_in, stages)),
        )

    return kernel


# ---------------------------------------------------------------------------
# pallas_call builders
# ---------------------------------------------------------------------------


def _hbm_spec():
    return pl.BlockSpec(memory_space=pl.ANY)


def _smem_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def map_pipeline_call(compute, n_scalars: int, n_in: int, *, x_shape, dtype,
                      num_stages: int = 2, block_rows: int = 64,
                      interpret: bool = False):
    """Build a pipelined elementwise-map ``pallas_call``.

    Inputs/outputs are full HBM-resident (rows, 128) arrays; scalars ride
    in SMEM as (1, 1) blocks.
    """
    rows = x_shape[0]
    block_rows = _fit_block(rows, block_rows)
    n_chunks = rows // block_rows
    stages = max(1, min(num_stages, n_chunks))
    kernel = _map_pipeline_kernel(
        compute, n_scalars, n_in, n_chunks=n_chunks, stages=stages,
        block_rows=block_rows, dtype=dtype)
    return pl.pallas_call(
        kernel,
        in_specs=[_smem_spec()] * n_scalars + [_hbm_spec()] * n_in,
        out_specs=_hbm_spec(),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), dtype),
        interpret=interpret,
    )


def reduce_pipeline_call(compute, n_in: int, *, x_shape, dtype,
                         num_stages: int = 2, block_rows: int = 64,
                         interpret: bool = False):
    """Build a pipelined reduction ``pallas_call`` -> (1, 128) per-lane
    partial sums."""
    rows = x_shape[0]
    block_rows = _fit_block(rows, block_rows)
    n_chunks = rows // block_rows
    stages = max(1, min(num_stages, n_chunks))
    acc_dtype = jnp.float32 if dtype in (jnp.bfloat16, jnp.float16) else dtype
    kernel = _reduce_pipeline_kernel(
        compute, n_in, n_chunks=n_chunks, stages=stages,
        block_rows=block_rows, dtype=dtype, acc_dtype=acc_dtype)
    return pl.pallas_call(
        kernel,
        in_specs=[_hbm_spec()] * n_in,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, LANES), acc_dtype),
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Halo pipeline (stencil kernels)
# ---------------------------------------------------------------------------


def _halo_pipeline_kernel(compute, *, n_chunks: int, stages: int,
                          block0: int, halo: int, fetch: int, in_rest: tuple,
                          out_rest: tuple, dtype):
    """Overlapping-fetch pipeline: chunk ``c`` fetches the padded rows
    ``[c*block0, c*block0 + fetch)`` and writes the disjoint output rows
    ``[c*block0, (c+1)*block0)``.

    ``compute(tile, g0)`` maps the ``(block0 + 2*halo, *in_rest)`` head of
    the fetched rows plus the chunk's global first output row to a
    ``(block0, *out_rest)`` block.  Same warm-up/steady/drain schedule as
    the map pipeline; overlapping *reads* are safe (each input row may be
    fetched by up to two chunks) and writes never overlap.
    """
    window = block0 + 2 * halo
    in_tail = (slice(None),) * len(in_rest)
    out_tail = (slice(None),) * len(out_rest)

    def kernel(in_ref, out_ref):
        def body(in_scr, out_scr, in_sem, out_sem):
            def in_dma(slot, chunk):
                return pltpu.make_async_copy(
                    in_ref.at[(pl.ds(chunk * block0, fetch),) + in_tail],
                    in_scr.at[slot],
                    in_sem.at[slot],
                )

            def out_dma(slot, chunk):
                return pltpu.make_async_copy(
                    out_scr.at[slot],
                    out_ref.at[(pl.ds(chunk * block0, block0),) + out_tail],
                    out_sem.at[slot],
                )

            for k in range(stages - 1):                      # warm-up
                in_dma(k, k).start()

            def loop(chunk, _):
                slot = jax.lax.rem(chunk, stages)
                ahead = chunk + stages - 1

                @pl.when(ahead < n_chunks)
                def _():
                    in_dma(jax.lax.rem(ahead, stages), ahead).start()

                in_dma(slot, chunk).wait()

                @pl.when(chunk >= stages)
                def _():
                    out_dma(slot, chunk - stages).wait()

                tile = in_scr[(slot, pl.ds(0, window)) + in_tail]
                out_scr[slot] = compute(tile, chunk * block0).astype(dtype)
                out_dma(slot, chunk).start()
                return ()

            jax.lax.fori_loop(0, n_chunks, loop, ())

            for k in range(min(stages, n_chunks)):           # drain
                chunk = n_chunks - 1 - k
                out_dma(chunk % stages, chunk).wait()

        pl.run_scoped(
            body,
            in_scr=pltpu.VMEM((stages, fetch) + in_rest, dtype),
            out_scr=pltpu.VMEM((stages, block0) + out_rest, dtype),
            in_sem=pltpu.SemaphoreType.DMA((stages,)),
            out_sem=pltpu.SemaphoreType.DMA((stages,)),
        )

    return kernel


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def halo_pipeline_call(compute, *, out_shape, in_shape, dtype, halo: int = 1,
                       num_stages: int = 2, block_rows: int = 8,
                       interpret: bool = False):
    """Build a pipelined halo-exchange stencil call.

    ``in_shape`` is the *pre-padded* input: axis 0 must be
    ``out_shape[0] + 2*halo`` (trailing dims are free — the caller decides
    how much spatial padding the compute callback expects).  Mosaic DMAs
    move whole (8, 128) tiles, so the returned callable zero-pads the input
    further: the last dim to a multiple of 128, the second-to-last to a
    multiple of 8 (for 2D arrays that is axis 0: each chunk's fetch window
    is rounded up to whole tiles).  ``compute`` therefore sees tiles at
    least as large as it asked for and slices what it needs.  On the chip
    the output's trailing dims must already be whole tiles (a 2D output's
    rows via ``block_rows``).  See the module docstring for the full
    pipeline contract.
    """
    rows = out_shape[0]
    if in_shape[0] != rows + 2 * halo:
        raise ValueError(
            f"padded input axis 0 must be rows + 2*halo = {rows + 2*halo}, "
            f"got {in_shape[0]}")
    block0 = _fit_block(rows, block_rows)
    n_chunks = rows // block0
    stages = max(1, min(num_stages, n_chunks))
    window = block0 + 2 * halo
    fetch = _round_up(window, 8) if len(in_shape) == 2 else window
    in_rest = list(in_shape[1:])
    in_rest[-1] = _round_up(in_rest[-1], LANES)
    if len(in_rest) >= 2:
        in_rest[-2] = _round_up(in_rest[-2], 8)
    pad = [(0, fetch - window)] + [(0, a - b) for a, b in
                                   zip(in_rest, in_shape[1:])]
    kernel = _halo_pipeline_kernel(
        compute, n_chunks=n_chunks, stages=stages, block0=block0, halo=halo,
        fetch=fetch, in_rest=tuple(in_rest), out_rest=tuple(out_shape[1:]),
        dtype=dtype)
    call = pl.pallas_call(
        kernel,
        in_specs=[_hbm_spec()],
        out_specs=_hbm_spec(),
        out_shape=jax.ShapeDtypeStruct(tuple(out_shape), dtype),
        interpret=interpret,
    )
    return lambda p: call(jnp.pad(p, pad))


# ---------------------------------------------------------------------------
# Fused multi-kernel chains
# ---------------------------------------------------------------------------
#
# Chaining two stream kernels through HBM costs the intermediate a full
# round trip (1 store + 1 load of every element).  Keeping it in VMEM
# drops those two streams, exactly as the ECM stream count predicts:
#
#   triad  A = B + s*C   {2 loads, 1 store}     5 streams total
#   update A = t*A       {1 load,  1 store}   (3 for triad + 2 for update)
#   fused  A = t*(B+s*C) {2 loads, 1 store}     3 streams total
#
# -> predicted memory-bound speedup 5/3 = 1.67x.


def fused_compute_triad_update(s, t, b, c):
    return t * (b + s * c)


def triad_update_chain_streams() -> tuple[int, int]:
    """(unfused, fused) HBM stream counts per element for triad->update."""
    return 5, 3
