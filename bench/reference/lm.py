"""Plain float32 reference of the decoder-only LM family, and its weights.

Independent of the program under test: nothing here imports ``repro``.
The weights are a function of ``(config, seed)`` alone, generated per
layer and per name, so the benchmark can hand the program one copy and
the reference can make its own after the program's state is freed.

The equations, per layer (pre-norm residual, as the configurations
publish them)::

    x = rmsnorm(h) * w_ln_attn
    q, k, v = x Wq + bq, x Wk + bk, x Wv + bv          # bias if qkv_bias
    q, k = rope(q), rope(k)        # first rope_fraction of each head,
                                   # adjacent dims paired
    a = softmax(q k^T / sqrt(head_dim) + causal mask) v
                                   # query head j reads KV head j // (H/KV)
    h = h + a Wo
    x = rmsnorm(h) * w_ln_ffn
    h = h + (silu(x Wgate) * (x Wup)) Wdown

then ``logits = (rmsnorm(h) * w_ln_f) Wunembed[:, :vocab]``.

Every matmul runs at ``Precision.HIGHEST`` (float32 on the TPU's MXU), so
the reference does not depend on ``jax.default_matmul_precision``.  With
``low=True`` the reference is computed in float8 (e4m3), one precision
step below the configurations' bfloat16 compute, as the program computes
in bfloat16: every matmul operand and output, the residual stream after
each add, and the logits are rounded to float8, while sums, norms and the
softmax run in float32.  That is the control.

``cfg`` is a configuration dict as in ``bench/configs/``, plus
``vocab_padded``: the rows of the embedding tables as the program holds
them (the reference reads only the first ``vocab``).
"""
from __future__ import annotations

import math
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: std of the QKV biases and of the noise on the norm weights: large
#: enough that dropping either moves the logits far beyond rounding
BIAS_STD = 0.5
NORM_NOISE = 0.1
#: query rows per attention block (bounds the (H, rows, T) score tile)
Q_BLOCK = 256
#: tokens per batch of sequences run through the layers together
TOKENS_PER_CHUNK = 32768


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def layer_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], str, float]]:
    """name -> (shape, kind, std) of one layer's weights.  ``kind`` is
    ``normal`` (N(0, std^2)) or ``norm`` (1 + N(0, std^2))."""
    d, h, kv, hd, f = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                       cfg["head_dim"], cfg["d_ff"])
    s_in = 1.0 / math.sqrt(d)
    out = {
        "ln_attn": ((d,), "norm", NORM_NOISE),
        "wq": ((d, h, hd), "normal", s_in),
        "wk": ((d, kv, hd), "normal", s_in),
        "wv": ((d, kv, hd), "normal", s_in),
        "wo": ((h, hd, d), "normal", 1.0 / math.sqrt(h * hd)),
        "ln_ffn": ((d,), "norm", NORM_NOISE),
        "w_gate": ((d, f), "normal", s_in),
        "w_up": ((d, f), "normal", s_in),
        "w_down": ((f, d), "normal", 1.0 / math.sqrt(f)),
    }
    if cfg.get("qkv_bias"):
        out["bq"] = ((h, hd), "normal", BIAS_STD)
        out["bk"] = ((kv, hd), "normal", BIAS_STD)
        out["bv"] = ((kv, hd), "normal", BIAS_STD)
    return out


def global_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], str, float]]:
    d, vp = cfg["d_model"], cfg["vocab_padded"]
    return {
        "embedding": ((vp, d), "normal", 1.0),
        "ln_f": ((d,), "norm", NORM_NOISE),
        "unembed": ((d, vp), "normal", 1.0 / math.sqrt(d)),
    }


def seed_key(seed: int):
    """A key from all bits of a non-negative seed of up to 64 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def weight(key, name: str, shape, kind: str, std: float, layer=0):
    """One weight: a pure function of (seed key, name, layer, shape)."""
    k = jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(name.encode())),
                           layer)
    x = jax.random.normal(k, shape, jnp.float32) * std
    return 1.0 + x if kind == "norm" else x


def layer_weights(cfg: dict, key, layer) -> dict:
    return {n: weight(key, n, *spec, layer=layer)
            for n, spec in layer_shapes(cfg).items()}


def global_weights(cfg: dict, key) -> dict:
    return {n: weight(key, n, *spec) for n, spec in global_shapes(cfg).items()}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _round(x, low: bool):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) if low else x


def _mm(spec: str, a, b, low: bool):
    out = jnp.einsum(spec, _round(a, low), _round(b, low), precision=HIGHEST)
    return _round(out, low)


def _rmsnorm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, positions, cfg: dict):
    hd = cfg["head_dim"]
    rot = int(hd * cfg.get("rope_fraction", 1.0))
    rot -= rot % 2
    if rot == 0:
        return x
    inv = 1.0 / (cfg["rope_theta"] ** (jnp.arange(0, rot, 2, jnp.float32)
                                        / rot))
    ang = positions[:, None].astype(jnp.float32) * inv       # (T, rot/2)
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    r = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1)
    return jnp.concatenate([r.reshape(*x.shape[:-1], rot), x[..., rot:]], -1)


def _attention(q, k, v, low: bool):
    """Causal GQA attention, in blocks of query rows.
    q: (n, T, H, hd); k, v: (n, T, KV, hd)."""
    n, t, h, hd = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    nb = -(-t // Q_BLOCK)
    qp = jnp.pad(q, ((0, 0), (0, nb * Q_BLOCK - t), (0, 0), (0, 0)))
    qb = qp.reshape(n, nb, Q_BLOCK, h, hd).transpose(1, 0, 2, 3, 4)
    cols = jnp.arange(t)

    def block(args):
        i, qi = args
        s = _mm("nqhd,nkhd->nhqk", qi, k, low) / math.sqrt(hd)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(rows[:, None] >= cols[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("nhqk,nkhd->nqhd", p, v, low)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.transpose(1, 0, 2, 3, 4).reshape(n, nb * Q_BLOCK, h, hd)[:, :t]


def layer(cfg: dict, w: dict, h, low: bool = False):
    """One layer over ``h`` (n, T, d), float32."""
    eps = cfg["norm_eps"]
    pos = jnp.arange(h.shape[1])
    x = _rmsnorm(h, w["ln_attn"], eps)
    q = _mm("ntd,dhk->nthk", x, w["wq"], low)
    k = _mm("ntd,dhk->nthk", x, w["wk"], low)
    v = _mm("ntd,dhk->nthk", x, w["wv"], low)
    if cfg.get("qkv_bias"):
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    a = _attention(_rope(q, pos, cfg), _rope(k, pos, cfg), v, low)
    h = _round(h + _mm("nthk,hkd->ntd", a, w["wo"], low), low)
    x = _rmsnorm(h, w["ln_ffn"], eps)
    g = _mm("ntd,df->ntf", x, w["w_gate"], low)
    u = _mm("ntd,df->ntf", x, w["w_up"], low)
    return _round(h + _mm("ntf,fd->ntd", jax.nn.silu(g) * u, w["w_down"], low),
                  low)


def head(cfg: dict, g: dict, h, low: bool = False):
    """Logits over the real vocabulary from final hidden states."""
    x = _rmsnorm(h, g["ln_f"], cfg["norm_eps"])
    return _mm("ntd,dv->ntv", x, g["unembed"][:, : cfg["vocab"]], low)


#: the configuration keys the reference reads
MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab", "vocab_padded", "qkv_bias", "rope_fraction",
              "rope_theta", "norm_eps")


@partial(jax.jit, static_argnums=0)
def _layer_weights(items, key, i):
    return layer_weights(dict(items), key, i)


@partial(jax.jit, static_argnums=(0, 3))
def _layer(items, w, h, low):
    return layer(dict(items), w, h, low)


@partial(jax.jit, static_argnums=0)
def _gaps(items, g, h, served):
    """Per sequence, the widest gap by which a served token's reference
    logit lies below the reference's best."""
    ref = head(dict(items), g, h)
    got = jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
    return jnp.max(jnp.max(ref, -1) - got, -1)


@partial(jax.jit, static_argnums=0)
def _control_top(items, g, h):
    """The tokens that the control's logits put first."""
    return jnp.argmax(head(dict(items), g, h, low=True), -1)


def _hidden(items, key, g, tokens, chunk, low):
    """Final hidden states of each chunk of sequences, layer by layer."""
    cfg = dict(items)
    hs = [_round(jnp.take(g["embedding"], jnp.asarray(tokens[s:s + chunk]),
                          axis=0), low)
          for s in range(0, len(tokens), chunk)]
    for i in range(cfg["n_layers"]):
        w = _layer_weights(items, key, i)
        hs = [_layer(items, w, h, low) for h in hs]
        del w
    return hs


def served_gaps(cfg: dict, seed: int, prompts: np.ndarray,
                served: np.ndarray, *, low: bool = False) -> np.ndarray:
    """The reference over each prompt followed by its served tokens.

    prompts: (N, P) int; served: (N, G + 1) int, the token served after the
    prompt and after each of the first G served tokens.  Returns (N,) gaps:
    for each sequence, the largest ``max(ref logits) - ref logit[token]``
    over its G + 1 served tokens (0 where every served token is the
    reference's best).  With ``low=True`` the tokens judged are the
    control's: those its own logits put first at each of those positions,
    teacher-forced on the same prompts and served tokens.
    Runs layer by layer, in chunks of sequences, so that it fits.
    """
    items = tuple((k, cfg[k]) for k in MODEL_KEYS)
    key = seed_key(seed)
    p = prompts.shape[1]
    tokens = np.concatenate([prompts, served[:, :-1]], 1).astype(np.int32)
    g = jax.jit(partial(global_weights, dict(items)))(key)
    chunk = max(1, TOKENS_PER_CHUNK // tokens.shape[1])
    judged = [jnp.asarray(served[s:s + chunk])
              for s in range(0, len(tokens), chunk)]
    if low:
        judged = [_control_top(items, g, h[:, p - 1:])
                  for h in _hidden(items, key, g, tokens, chunk, True)]
    hs = _hidden(items, key, g, tokens, chunk, False)
    return np.concatenate([np.asarray(_gaps(items, g, h[:, p - 1:], t))
                           for h, t in zip(hs, judged)])
