"""The serve path's copy of the parameters (``ArchDef.serving_params``).

``generate`` casts the weights that the LM family's code casts to the
compute dtype at every use once per call, and its steps read that copy.
The rounding is the same as at the use, so what is served must be
bitwise what the steps give when they are fed the stored float32 tree.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch.serve import generate, serve_steps
from repro.models import lm
from repro.models.common import materialize

GEN = 3
PROMPT = 8


def _arch(name: str, **fields):
    arch = get_arch(name, smoke=True)
    return dataclasses.replace(arch, cfg=dataclasses.replace(arch.cfg,
                                                             **fields))


def _params(arch, seed: int = 0):
    """Stored float32 parameters with every leaf perturbed, so that norm
    weights and biases are not 1 and 0 and few values are exact in
    bfloat16."""
    params = materialize(arch.param_spec(), jax.random.key(seed))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        w + 0.1 * jax.random.normal(k, w.shape, w.dtype)
        for k, w in zip(keys, leaves)])


SERVED = [("internlm2-1.8b", {}),
          ("glm4-9b", {"qkv_bias": True}),
          ("granite-moe-1b-a400m", {})]


@pytest.mark.parametrize("name,fields", SERVED,
                         ids=[name for name, _ in SERVED])
def test_generate_serves_what_the_stored_tree_gives(name, fields):
    arch = _arch(name, **fields)
    params = _params(arch)
    batch = {"tokens": jax.random.randint(jax.random.key(2), (2, PROMPT), 0,
                                          arch.cfg.vocab)}
    max_len = PROMPT + GEN + 4
    out = generate(arch, serve_steps(arch, max_len), params, batch, GEN)

    prefill = jax.jit(lambda p, b: lm.prefill(p, arch.cfg, b,
                                              max_len=max_len))
    decode = jax.jit(lambda p, c, b: lm.decode_step(p, arch.cfg, c, b))
    logits, cache = prefill(params, batch)
    want_logits, want_tokens = [logits], []
    for _ in range(GEN + 1):
        want_tokens.append(jnp.argmax(want_logits[-1][:, -1, :arch.cfg.vocab],
                                      -1)[:, None].astype(jnp.int32))
        if len(want_tokens) <= GEN:
            logits, cache = decode(params, cache,
                                   {"tokens": want_tokens[-1]})
            want_logits.append(logits)

    np.testing.assert_array_equal(
        out["tokens"], np.asarray(jnp.concatenate(want_tokens, 1)))
    assert len(out["logits"]) == len(want_logits) == GEN + 1
    for got, want in zip(out["logits"], want_logits):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("name", ["internlm2-1.8b", "glm4-9b",
                                  "granite-moe-1b-a400m"])
def test_lm_serving_params_cast_what_the_steps_cast(name):
    arch = _arch(name, **dict(SERVED)[name])
    params = _params(arch)
    served = arch.serving_params(params)
    assert jax.tree.structure(served) == jax.tree.structure(params)
    for path, w in jax.tree_util.tree_flatten_with_path(served)[0]:
        keys = [p.key for p in path]
        stored = params
        for k in keys:
            stored = stored[k]
        want = (jnp.float32 if keys[-1] in ("embedding", "router")
                else arch.cfg.dtype)
        assert w.dtype == want, (keys, w.dtype)
        assert w.shape == stored.shape
        np.testing.assert_array_equal(np.asarray(w),
                                      np.asarray(stored.astype(want)))
    assert served["embedding"] is params["embedding"]
    routers = [p for p, _ in jax.tree_util.tree_flatten_with_path(served)[0]
               if p[-1].key == "router"]
    assert bool(routers) == (arch.cfg.moe is not None)


@pytest.mark.parametrize("name", ["zamba2-1.2b", "xlstm-125m",
                                  "whisper-base"])
def test_other_families_serve_their_stored_tree(name):
    arch = get_arch(name, smoke=True)
    params = materialize(arch.param_spec(), jax.random.key(0))
    assert arch.serving_params(params) is params
