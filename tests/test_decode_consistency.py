"""Decode path == full forward: prefill + token-by-token decode must
reproduce the teacher-forced logits (exercises the KV cache, the GQA
grouped einsums and the cache-length masking)."""
import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models.common import materialize


@pytest.mark.parametrize("name", ["internlm2-1.8b", "glm4-9b",
                                  "qwen1.5-110b", "granite-moe-1b-a400m"])
def test_lm_decode_matches_full_forward(name):
    from repro.models import lm

    arch = get_arch(name, smoke=True)
    cfg = arch.cfg
    params = materialize(arch.param_spec(), jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 12), 0, cfg.vocab)

    h, _ = lm.hidden_states(params, cfg, tokens)
    full = np.asarray(lm.logits_fn(params, cfg, h), np.float32)

    logits, cache = lm.prefill(params, cfg, {"tokens": tokens[:, :8]},
                               max_len=16)
    np.testing.assert_allclose(np.asarray(logits[:, 0], np.float32),
                               full[:, 7], rtol=6e-2, atol=6e-2)
    for t in range(8, 12):
        logits, cache = lm.decode_step(params, cfg, cache,
                                       {"tokens": tokens[:, t:t + 1]})
        np.testing.assert_allclose(np.asarray(logits[:, 0], np.float32),
                                   full[:, t], rtol=6e-2, atol=6e-2,
                                   err_msg=f"{name} step {t}")


def test_whisper_decode_matches_teacher_forced():
    from repro.models import whisper

    arch = get_arch("whisper-base", smoke=True)
    cfg = arch.cfg
    params = materialize(arch.param_spec(), jax.random.key(0))
    frames = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model)) * 0.1
    tokens = jax.random.randint(jax.random.key(2), (2, 10), 0, cfg.vocab)

    enc = whisper.encode(params, cfg, frames)
    h = whisper.decode_train(params, cfg, tokens, enc)
    full = np.asarray(whisper._logits(params, cfg, h), np.float32)

    logits, cache = whisper.prefill(
        params, cfg, {"frames": frames, "tokens": tokens[:, :6]}, max_len=12)
    np.testing.assert_allclose(np.asarray(logits[:, 0], np.float32),
                               full[:, 5], rtol=6e-2, atol=6e-2)
    for t in range(6, 10):
        logits, cache = whisper.decode_step(params, cfg, cache,
                                            {"tokens": tokens[:, t:t + 1]})
        np.testing.assert_allclose(np.asarray(logits[:, 0], np.float32),
                                   full[:, t], rtol=6e-2, atol=6e-2,
                                   err_msg=f"step {t}")


def test_decode_matches_fresh_prefill_after_long_chunked_prompt():
    """internlm2 as the full config runs it (chunked attention), at smoke
    width and 4 layers: after a 512-token prompt each decode step's logits
    match a fresh prefill over the prompt plus the tokens decoded so far,
    within the bf16 bound chip_smoke.py holds the full width to.  A
    softmax made one-hot by too wide an attention init fails this: the two
    paths round q*scale at different points, which flips near-tied keys."""
    import dataclasses

    arch = get_arch("internlm2-1.8b", smoke=True)
    cfg = dataclasses.replace(arch.cfg, attn_impl="chunked", n_layers=4)
    arch = dataclasses.replace(arch, cfg=cfg)
    params = materialize(arch.param_spec(), jax.random.key(0))
    prompt = np.asarray(jax.random.randint(jax.random.key(1), (2, 512), 0,
                                           cfg.vocab))
    logits, cache = jax.jit(lambda p, b: arch.prefill(p, b, max_len=520))(
        params, {"tokens": prompt})
    fresh = jax.jit(lambda p, b: arch.prefill(p, b))
    decode = jax.jit(arch.decode)
    seq = prompt
    for _ in range(4):
        tok = np.asarray(jax.numpy.argmax(logits[:, -1, :cfg.vocab], -1),
                         np.int32)[:, None]
        seq = np.concatenate([seq, tok], 1)
        logits, cache = decode(params, cache, {"tokens": tok})
        want = np.asarray(fresh(params, {"tokens": seq})[0][:, 0, :cfg.vocab],
                          np.float32)
        got = np.asarray(logits[:, 0, :cfg.vocab], np.float32)
        assert np.max(np.abs(got - want)) <= 2.0**-4 * np.max(np.abs(want))
