"""Decoder-only transformer LM family.

Covers internlm2-1.8b, qwen1.5-110b, minitron-4b, glm4-9b (dense, GQA,
optional QKV bias / partial RoPE), granite-moe / qwen3-moe (MoE FFN via
``repro.models.moe``) and pixtral-12b (multimodal: precomputed patch
embeddings prepended to the token stream — the vision frontend is a stub
input per the brief).

Layers are scanned (``lax.scan`` over parameters stacked on a leading
"layers" axis) with configurable remat, so HLO size is O(1) in depth and
94-layer configs compile quickly.

Every step names its parts with ``jax.named_scope``: ``attn`` (attention
norm, attention, and in decode the K/V cache slice and update), ``mlp``
(FFN norm and FFN) and ``head`` (final norm and logits).  They add
metadata (the compiled HLO's ``op_name``), not computation, so a profile
can split device time by them.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from .attention import AttnConfig, attn_spec, attention, decode_attention
from .common import (
    ParamSpec,
    embed,
    embedding_spec,
    rmsnorm,
    rmsnorm_spec,
    shard_annotate,
    swiglu,
    swiglu_spec,
    unembed,
    unembed_spec,
)
from .moe import MoEConfig, moe_ffn, moe_spec


def pad_vocab(vocab: int, multiple: int = 2048) -> int:
    return ((vocab + multiple - 1) // multiple) * multiple


@dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                  # 0 -> d_model // n_heads
    qkv_bias: bool = False
    rope_fraction: float = 1.0
    rope_theta: float = 10000.0
    moe: MoEConfig | None = None
    attn_impl: str = "dense"           # dense | chunked | flash
    attn_chunk: int = 1024
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: str = "none"                # none | full | dots
    scan_layers: bool = True
    image_prefix: int = 0              # pixtral: # of patch positions
    vocab_pad_multiple: int = 2048
    z_loss: float = 0.0

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def vocab_padded(self) -> int:
        return pad_vocab(self.vocab, self.vocab_pad_multiple)

    @property
    def attn_cfg(self) -> AttnConfig:
        return AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.head_dim_,
            qkv_bias=self.qkv_bias, rope_fraction=self.rope_fraction,
            rope_theta=self.rope_theta, impl=self.attn_impl,
            chunk_size=self.attn_chunk)


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------


def _layer_spec(cfg: LMConfig) -> dict:
    spec = {
        "ln_attn": rmsnorm_spec(cfg.d_model),
        "attn": attn_spec(cfg.attn_cfg),
        "ln_ffn": rmsnorm_spec(cfg.d_model),
    }
    if cfg.moe is not None:
        spec["moe"] = moe_spec(cfg.d_model, cfg.moe)
    else:
        spec["mlp"] = swiglu_spec(cfg.d_model, cfg.d_ff)
    return spec


def _stack_spec(spec, n: int):
    return jax.tree.map(
        lambda s: ParamSpec((n, *s.shape), ("layers", *s.axes), init=s.init,
                            scale=s.scale, dtype=s.dtype),
        spec, is_leaf=lambda x: isinstance(x, ParamSpec))


def lm_spec(cfg: LMConfig) -> dict:
    layer = _layer_spec(cfg)
    return {
        "embedding": embedding_spec(cfg.vocab_padded, cfg.d_model),
        "layers": _stack_spec(layer, cfg.n_layers) if cfg.scan_layers
        else {f"layer_{i}": layer for i in range(cfg.n_layers)},
        "ln_f": rmsnorm_spec(cfg.d_model),
        "unembed": unembed_spec(cfg.d_model, cfg.vocab_padded),
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _ffn(p_layer, cfg: LMConfig, h):
    if cfg.moe is not None:
        from repro.dist.sharding import current_context
        ctx = current_context()
        fsdp = None
        if (cfg.moe.impl == "shard_map" and ctx.profile is not None
                and ctx.profile.rules.get("embed") == "data"):
            fsdp = "data"
        out, aux = moe_ffn(p_layer["moe"], cfg.moe, h,
                           mesh=ctx.mesh, data_axes=ctx.data_axes,
                           fsdp_axis=fsdp)
        return out, aux
    return swiglu(p_layer["mlp"], h), 0.0


def _layer_body(cfg: LMConfig):
    def body(h, p_l):
        # barrier: stops XLA from hoisting the rmsnorm bf16->f32 convert of
        # the *entire* saved-carry stack out of the backward while-loop
        # (observed 2x carry-stack memory on the dry-run without it).  Its
        # differentiation rule puts the same barrier on the cotangent.
        h = jax.lax.optimization_barrier(h)
        with jax.named_scope("attn"):
            a, _ = attention(p_l["attn"], cfg.attn_cfg,
                             rmsnorm(p_l["ln_attn"], h, cfg.norm_eps))
        h = h + a
        with jax.named_scope("mlp"):
            f, aux = _ffn(p_l, cfg, rmsnorm(p_l["ln_ffn"], h, cfg.norm_eps))
        h = h + f
        h = shard_annotate(h, ("batch", "seq", "embed"))
        return h, aux
    return body


def _remat(fn, cfg: LMConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def hidden_states(params, cfg: LMConfig, tokens, *, extra_embeds=None):
    """Token (+ optional prefix) embeddings through all layers."""
    h = embed(params["embedding"], tokens).astype(cfg.dtype)
    if extra_embeds is not None:
        h = jnp.concatenate([extra_embeds.astype(cfg.dtype), h], axis=1)
    h = shard_annotate(h, ("batch", "seq", "embed"))
    body = _layer_body(cfg)
    if cfg.scan_layers:
        wrapped = _remat(body, cfg)
        h, aux = jax.lax.scan(wrapped, h, params["layers"])
        aux = jnp.sum(aux)
    else:
        aux = 0.0
        for i in range(cfg.n_layers):
            step = _remat(body, cfg)
            h, a = step(h, params["layers"][f"layer_{i}"])
            aux = aux + a
    with jax.named_scope("head"):
        return rmsnorm(params["ln_f"], h, cfg.norm_eps), aux


def logits_fn(params, cfg: LMConfig, h):
    logits = unembed(params["unembed"], h)
    logits = shard_annotate(logits, ("batch", None, "vocab"))
    return logits


def loss_fn(params, cfg: LMConfig, batch):
    """batch: tokens (B,S), labels (B,S), mask (B,S).  For VLM configs,
    ``patch_embeds`` (B,P,d) is prepended and labels cover the full
    (P + S_text) sequence."""
    h, aux = hidden_states(params, cfg, batch["tokens"],
                           extra_embeds=batch.get("patch_embeds"))
    with jax.named_scope("head"):
        logits = logits_fn(params, cfg, h)
    labels = batch["labels"]
    mask = batch.get("mask")
    loss = masked_xent(logits, labels, mask, cfg)
    loss = loss + 0.01 * aux
    return loss, {"loss": loss, "aux_loss": aux}


def masked_xent(logits, labels, mask, cfg: LMConfig):
    from .common import masked_xent as _mx
    return _mx(logits, labels, mask, vocab=cfg.vocab,
               vocab_padded=cfg.vocab_padded, z_loss=cfg.z_loss)


# ---------------------------------------------------------------------------
# prefill / decode (KV cache)
# ---------------------------------------------------------------------------


def cache_spec(cfg: LMConfig, batch: int, max_len: int) -> dict:
    kvh, hd = cfg.n_kv_heads, cfg.head_dim_
    shape = (cfg.n_layers, batch, max_len, kvh, hd)
    axes = ("layers", "batch", "seq", "kv_heads", "head_dim")
    return {
        "k": ParamSpec(shape, axes, init="zeros", dtype=cfg.dtype),
        "v": ParamSpec(shape, axes, init="zeros", dtype=cfg.dtype),
        "length": ParamSpec((), (), init="zeros", dtype=jnp.int32),
    }


def serving_params(params, cfg: LMConfig):
    """The tree ``prefill`` and ``decode_step`` read in serving: every
    weight they cast to ``cfg.dtype`` at use (attention, MLP and expert
    weights, biases, norms, ``unembed``) cast once, in one jitted program,
    so that each step reads 2 bytes a parameter and no longer converts
    the stored 4.  The rounding is the same, so the steps' results are.

    Left as stored: ``embedding``, of which a step gathers a few rows and
    casts them (a copy of the whole table would cost more than it saves);
    each MoE ``router`` goes to ``router_dtype``, in which its logits are
    computed."""
    served = {k: v for k, v in params.items() if k != "embedding"}
    return {**_serving_cast(served, cfg), "embedding": params["embedding"]}


@partial(jax.jit, static_argnums=1)
def _serving_cast(params, cfg: LMConfig):
    def cast(path, w):
        if path[-1].key == "router":
            return w.astype(cfg.moe.router_dtype)
        return w.astype(cfg.dtype)
    return jax.tree_util.tree_map_with_path(cast, params)


def prefill(params, cfg: LMConfig, batch, *, max_len: int | None = None):
    """Process the prompt, return (logits_last, cache).

    Uses the full-sequence path and collects per-layer K/V (right-padded to
    ``max_len`` for subsequent decode).  Only scanned layers are supported
    here (all assigned archs use scan).
    """
    assert cfg.scan_layers
    tokens = batch["tokens"]
    h = embed(params["embedding"], tokens).astype(cfg.dtype)
    if batch.get("patch_embeds") is not None:
        h = jnp.concatenate([batch["patch_embeds"].astype(cfg.dtype), h], 1)
    h = shard_annotate(h, ("batch", "seq", "embed"))

    def body(hh, p_l):
        with jax.named_scope("attn"):
            a, (k, v) = attention(p_l["attn"], cfg.attn_cfg,
                                  rmsnorm(p_l["ln_attn"], hh, cfg.norm_eps))
        hh = hh + a
        with jax.named_scope("mlp"):
            f, _ = _ffn(p_l, cfg, rmsnorm(p_l["ln_ffn"], hh, cfg.norm_eps))
        hh = hh + f
        hh = shard_annotate(hh, ("batch", "seq", "embed"))
        return hh, (k.astype(cfg.dtype), v.astype(cfg.dtype))

    h, (ks, vs) = jax.lax.scan(_remat(body, cfg), h, params["layers"])
    with jax.named_scope("head"):
        h = rmsnorm(params["ln_f"], h, cfg.norm_eps)
        logits = logits_fn(params, cfg, h[:, -1:, :])
    s = tokens.shape[1] + (batch["patch_embeds"].shape[1]
                           if batch.get("patch_embeds") is not None else 0)
    if max_len is not None and max_len > s:
        pad = ((0, 0), (0, 0), (0, max_len - s), (0, 0), (0, 0))
        ks = jnp.pad(ks, pad)
        vs = jnp.pad(vs, pad)
    cache = {"k": ks, "v": vs, "length": jnp.asarray(s, jnp.int32)}
    return logits, cache


def decode_step(params, cfg: LMConfig, cache, batch):
    """One-token decode.  batch: tokens (B,1).  cache as in cache_spec.

    The full (L, B, S, kvh, hd) cache rides the layer scan as a *carry*
    (updated in place at the loop index) rather than as xs/ys: stacked ys
    cannot alias their input, which double-buffers the cache — measured
    +2x cache bytes of temp on the qwen1.5-110b decode_32k dry-run."""
    assert cfg.scan_layers
    tokens = batch["tokens"]
    h = embed(params["embedding"], tokens).astype(cfg.dtype)
    h = shard_annotate(h, ("batch", None, "embed"))
    length = cache["length"]

    def body(carry, xs):
        hh, kc, vc = carry
        p_l, i = xs
        with jax.named_scope("attn"):
            ck = jax.lax.dynamic_index_in_dim(kc, i, 0, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(vc, i, 0, keepdims=False)
            a, ck, cv = decode_attention(
                p_l["attn"], cfg.attn_cfg,
                rmsnorm(p_l["ln_attn"], hh, cfg.norm_eps), ck, cv, length)
            kc = jax.lax.dynamic_update_index_in_dim(kc, ck, i, 0)
            vc = jax.lax.dynamic_update_index_in_dim(vc, cv, i, 0)
        hh = hh + a
        with jax.named_scope("mlp"):
            f, _ = _ffn(p_l, cfg, rmsnorm(p_l["ln_ffn"], hh, cfg.norm_eps))
        hh = hh + f
        return (hh, kc, vc), None

    (h, ks, vs), _ = jax.lax.scan(
        body, (h, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.n_layers)))
    with jax.named_scope("head"):
        h = rmsnorm(params["ln_f"], h, cfg.norm_eps)
        logits = logits_fn(params, cfg, h)
    return logits, {"k": ks, "v": vs, "length": length + 1}
