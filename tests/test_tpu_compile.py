"""Compile the main path for a described TPU v5e chip (nothing runs).

The TPU compiler is installed without a chip: it compiles for a described
``v5e:2x2`` topology and refuses what the chip would refuse — a DMA slice
not aligned to the (8, 128) tiling, more VMEM than a kernel may use, a
program larger than the chip's 16 GB.  Interpret mode catches none of
these.  Every case here compiles one Pallas kernel at a real size (and
finds its ``tpu_custom_call`` in the executable) or one full-width
internlm2-1.8b serve step (and checks that it fits the chip).

The topology is described inside a module fixture, never at import: only
the one test worker given this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.attention import ops as attn_ops
from repro.kernels.matmul import ops as mm_ops
from repro.kernels.stencil import ops as stencil_ops
from repro.kernels.stream import ops as stream_ops
from repro.models.common import abstract

N = 1 << 26                      # f32 elements per stream (256 MiB)
STAGES = [None, 1, 2, 3]
HBM_BYTES = 16e9                 # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host, with JAX's persistent
    compilation cache off: a compile for a described chip is written to
    the cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _stream_call(name, ns, sh):
    x = jax.ShapeDtypeStruct((N,), jnp.float32, sharding=sh)
    s = jax.ShapeDtypeStruct((), jnp.float32, sharding=sh)
    kw = dict(num_stages=ns, interpret=False)
    S = stream_ops
    return {
        "load": (lambda a: S.load(a, **kw), x),
        "ddot": (lambda a, b: S.ddot(a, b, **kw), x, x),
        "store": (lambda v: S.store(v, (N,), jnp.float32, **kw), s),
        "update": (lambda v, a: S.update(v, a, **kw), s, x),
        "copy": (lambda a: S.copy(a, **kw), x),
        "striad": (lambda v, a, b: S.striad(v, a, b, **kw), s, x, x),
        "schoenauer": (lambda a, b, c: S.schoenauer(a, b, c, **kw), x, x, x),
    }[name]


@pytest.mark.parametrize("ns", STAGES)
@pytest.mark.parametrize("name", ["load", "ddot", "store", "update", "copy",
                                  "striad", "schoenauer"])
def test_stream_kernel_compiles(one_chip, name, ns):
    fn, *shapes = _stream_call(name, ns, one_chip)
    assert "tpu_custom_call" in _compile(fn, *shapes).as_text()


@pytest.mark.parametrize("ns", STAGES)
def test_jacobi2d_8192_compiles(one_chip, ns):
    a = jax.ShapeDtypeStruct((8192, 8192), jnp.float32, sharding=one_chip)
    c = _compile(lambda x: stencil_ops.jacobi2d(x, num_stages=ns,
                                                interpret=False), a)
    assert "tpu_custom_call" in c.as_text()


def test_matmul_4096_bf16_compiles(one_chip):
    x = jax.ShapeDtypeStruct((4096, 4096), jnp.bfloat16, sharding=one_chip)
    c = _compile(lambda a, b: mm_ops.matmul(a, b, interpret=False), x, x)
    assert "tpu_custom_call" in c.as_text()


def test_flash_attention_internlm2_prefill_compiles(one_chip):
    cfg = get_arch("internlm2-1.8b", smoke=False).cfg
    q = jax.ShapeDtypeStruct((4, 512, cfg.n_heads, cfg.head_dim_),
                             jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((4, 512, cfg.n_kv_heads, cfg.head_dim_),
                              jnp.bfloat16, sharding=one_chip)
    c = _compile(lambda a, b, d: attn_ops.flash_attention(
        a, b, d, causal=True, interpret=False), q, kv, kv)
    assert "tpu_custom_call" in c.as_text()


def _serve_step(one_chip, step, weights=lambda arch, stored: stored):
    """A full-width serve step of ``chip_smoke.py`` (4 requests, 512 prompt
    tokens, 16 decoded), compiled for one chip, with ``weights`` made
    from the stored parameters' shapes; also returns those shapes."""
    arch = get_arch("internlm2-1.8b", smoke=False)
    batch, prompt, max_len = 4, 512, 512 + 16 + 8

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    stored = place(abstract(arch.param_spec()))
    params = place(weights(arch, stored))
    if step == "prefill":
        tokens = jax.ShapeDtypeStruct((batch, prompt), jnp.int32,
                                      sharding=one_chip)
        return _compile(lambda p, t: arch.prefill(p, {"tokens": t},
                                                  max_len=max_len),
                        params, tokens), stored
    cache = place(abstract(arch.cache_spec(batch, max_len)))
    tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one_chip)
    return _compile(lambda p, kv, t: arch.decode(p, kv, {"tokens": t}),
                    params, cache, tokens), stored


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_internlm2_serve_step_fits_one_chip(one_chip, step):
    """The full-width serve steps compile, and their arguments plus
    temporaries fit one chip's HBM."""
    c, _ = _serve_step(one_chip, step)
    mem = c.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 7e9 < mem.argument_size_in_bytes          # f32 weights: real
    assert used < HBM_BYTES, used


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_internlm2_serve_step_on_the_serving_copy(one_chip, step):
    """Fed ``arch.serving_params``' bf16 copy, as ``generate`` feeds them,
    the full-width serve steps convert no stack of weights, and the stored
    f32 tree, the copy and the step's temporaries fit one chip."""
    c, stored = _serve_step(
        one_chip, step,
        lambda arch, stored: jax.eval_shape(arch.serving_params, stored))
    n_layers = get_arch("internlm2-1.8b", smoke=False).cfg.n_layers
    assert not re.search(rf"= bf16\[{n_layers},\S* convert\(", c.as_text())
    mem = c.memory_analysis()
    stored_bytes = sum(s.size * s.dtype.itemsize
                       for s in jax.tree.leaves(stored))
    assert mem.argument_size_in_bytes < stored_bytes / 1.5
    used = (stored_bytes + mem.argument_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
