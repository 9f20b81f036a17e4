"""The plain reference agrees with the program before it judges it.

At a size the CPU runs in seconds, with GLM-4's mechanisms (QKV bias,
partial RoPE, 4:1 GQA) and nonzero biases and norm weights, the program's
prefill and its decode steps through the KV cache, computed in float32,
give the reference's logits at every position.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.programs import lm as program
from bench.reference import lm as ref
from bench.tests.conftest import TINY_CONFIG

SEED = 2**33 + 5
#: float32 on both sides; only the order of the sums differs
RTOL = 2e-4


def _program_f32(cfg):
    arch = program.build_arch(cfg)
    return dataclasses.replace(
        arch, cfg=dataclasses.replace(arch.cfg, dtype=jnp.float32,
                                      attn_impl="chunked", attn_chunk=8))


def _reference_logits(mcfg, seed, tokens):
    key = ref.seed_key(seed)
    g = ref.global_weights(mcfg, key)
    h = jnp.take(g["embedding"], jnp.asarray(tokens), axis=0)
    for i in range(mcfg["n_layers"]):
        h = ref.layer(mcfg, ref.layer_weights(mcfg, key, i), h)
    return np.asarray(ref.head(mcfg, g, h))


@pytest.mark.parametrize("cfg", [
    TINY_CONFIG,
    {**TINY_CONFIG, "qkv_bias": False, "rope_fraction": 1.0,
     "n_kv_heads": 2, "rope_theta": 1e6},
], ids=["glm4-like", "internlm2-like"])
def test_prefill_then_decode_match_reference(cfg):
    arch = _program_f32(cfg)
    mcfg = program.model_cfg(cfg, arch)
    params = program.make_params(arch, mcfg, SEED, None)
    assert float(jnp.abs(params["layers"]["ln_attn"] - 1).max()) > 0.05
    if cfg["qkv_bias"]:
        assert float(jnp.abs(params["layers"]["attn"]["bq"]).max()) > 0.5
    rng = np.random.default_rng(0)
    b, p, g = 3, 16, 5
    tokens = rng.integers(0, cfg["vocab"], (b, p + g), dtype=np.int32)
    want = _reference_logits(mcfg, SEED, tokens)[..., : cfg["vocab"]]

    logits, cache = arch.prefill(params, {"tokens": jnp.asarray(tokens[:, :p])},
                                 max_len=p + g + 3)
    got = [logits[:, -1]]
    for i in range(g):
        logits, cache = arch.decode(params, cache,
                                    {"tokens": jnp.asarray(tokens[:, p + i:
                                                                  p + i + 1])})
        got.append(logits[:, -1])
    got = np.stack([np.asarray(x)[:, : cfg["vocab"]] for x in got], 1)
    scale = np.abs(want[:, p - 1:]).max()
    np.testing.assert_allclose(got, want[:, p - 1:], rtol=0,
                               atol=RTOL * scale)


def test_weights_are_a_function_of_seed_name_and_layer():
    mcfg = {**TINY_CONFIG, "vocab_padded": 256}
    key = ref.seed_key(SEED)
    stacked = jax.vmap(lambda i: ref.layer_weights(mcfg, key, i))(
        jnp.arange(2))
    one = ref.layer_weights(mcfg, key, 1)
    for name in one:
        np.testing.assert_array_equal(stacked[name][1], one[name])
    other = ref.layer_weights(mcfg, ref.seed_key(SEED + 2**32), 1)
    assert not np.array_equal(other["wq"], one["wq"])


def test_served_gaps_zero_for_reference_tokens_and_positive_otherwise():
    mcfg = {**TINY_CONFIG, "vocab_padded": 256}
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, 256, (2, 12), dtype=np.int32)
    # greedy continuation under the reference itself
    seq = prompts
    for _ in range(4):
        nxt = _reference_logits(mcfg, SEED, seq)[:, -1, :256].argmax(-1)
        seq = np.concatenate([seq, nxt[:, None].astype(np.int32)], 1)
    served = seq[:, 12:]
    served = np.concatenate(
        [served, _reference_logits(mcfg, SEED, seq)[:, -1, :256].argmax(
            -1)[:, None]], 1)
    gaps = ref.served_gaps(mcfg, SEED, prompts, served)
    assert np.all(gaps < 1e-5)
    bad = served.copy()
    bad[1, 2] = (bad[1, 2] + 1) % 256
    gaps = ref.served_gaps(mcfg, SEED, prompts, bad)
    assert gaps[0] < 1e-5 and gaps[1] > 1e-3
