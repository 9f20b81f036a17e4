"""GQA attention: dense, chunked (flash-style in pure XLA ops) and Pallas
implementations, plus KV-cache decode.

``impl`` selection:

* ``dense``   — materialises the (Sq, Sk) scores; fine for smoke tests and
  short sequences.
* ``chunked`` — online-softmax over KV chunks via ``lax.scan``: the flash
  attention *algorithm* expressed in XLA ops, so it compiles on any backend
  and keeps HBM traffic/score memory at O(S·chunk).  This is what the big
  dry-run configs use.
* ``flash``   — the Pallas kernel (``repro.kernels.attention``), TPU runtime.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import ParamSpec, apply_rope, rope_angles, shard_annotate

NEG_INF = -1e30


@dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    rope_fraction: float = 1.0
    rope_theta: float = 10000.0
    causal: bool = True
    impl: str = "dense"          # dense | chunked | flash
    chunk_size: int = 1024


def attn_spec(cfg: AttnConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    # fan-in is d for the input projections and h*hd for the output one;
    # the default (the last-but-one dim: heads / head_dim) is up to 11x too
    # wide at published widths, which makes every softmax one-hot and the
    # logits chaotic in bf16 rounding
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(h * hd)
    p = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim"),
                        scale=s_in),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"),
                        scale=s_in),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"),
                        scale=s_in),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed"),
                        scale=s_out),
    }
    if cfg.qkv_bias:
        p["bq"] = ParamSpec((h, hd), ("heads", "head_dim"), init="zeros")
        p["bk"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"), init="zeros")
        p["bv"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"), init="zeros")
    return p


def _qkv(p, cfg: AttnConfig, x, positions):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if cfg.rope_fraction > 0:
        cos, sin, rot = rope_angles(positions, cfg.head_dim,
                                    theta=cfg.rope_theta,
                                    fraction=cfg.rope_fraction)
        # rope math in f32 (cos/sin), result back in the compute dtype so
        # the residual stream stays bf16 (scan carries are dtype-strict)
        q = apply_rope(q, cos, sin, rot).astype(dt)
        k = apply_rope(k, cos, sin, rot).astype(dt)
    q = shard_annotate(q, ("batch", None, "heads", None))
    k = shard_annotate(k, ("batch", None, "kv_heads", None))
    v = shard_annotate(v, ("batch", None, "kv_heads", None))
    return q, k, v


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.repeat(k, n_rep, axis=2)


def _dense_attn(q, k, v, *, causal: bool, q_offset=0):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        rows = q_offset + jnp.arange(sq)[:, None]
        cols = jnp.arange(sk)[None, :]
        s = jnp.where(rows >= cols, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _chunked_attn(q, k, v, *, causal: bool, chunk: int):
    """Online-softmax over (q-block x kv-chunk) tiles: the flash algorithm
    expressed in XLA ops (double ``lax.scan``), GQA-aware (KV heads are
    never repeated — the q-group dim rides along in the einsums).

    Score tiles are (B, kvH, rep, cq, ck): O(chunk^2), never O(S^2).
    """
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    cq = min(chunk, sq)
    ck = min(chunk, sk)
    assert sq % cq == 0 and sk % ck == 0, (sq, sk, chunk)
    nq, nk = sq // cq, sk // ck
    scale = 1.0 / math.sqrt(d)
    # keep q/k/v in the compute dtype; f32 appears only in score/accumulator
    # tiles (a full-sequence f32 copy would double the remat carry stack)
    qg = (q * jnp.asarray(scale, q.dtype)).reshape(b, nq, cq, kvh, rep, d)
    qg = qg.transpose(1, 0, 2, 3, 4, 5)                     # (nq,b,cq,kvh,rep,d)
    kc = k.reshape(b, nk, ck, kvh, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nk, ck, kvh, d).transpose(1, 0, 2, 3, 4)

    def q_block(_, qin):
        qi, qb = qin                                         # qb: (b,cq,kvh,rep,d)
        rows = qi * cq + jnp.arange(cq)

        def kv_chunk(carry, kin):
            m, l, acc = carry
            ki, kb, vb = kin
            s = jnp.einsum("bqhrd,bkhd->bhrqk", qb, kb,
                           preferred_element_type=jnp.float32)
            if causal:
                cols = ki * ck + jnp.arange(ck)
                mask = (rows[:, None] >= cols[None, :])[None, None, None]
                s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhrqk,bkhd->bhrqd", p.astype(v.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        m0 = jnp.full((b, kvh, rep, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, rep, cq), jnp.float32)
        a0 = jnp.zeros((b, kvh, rep, cq, d), jnp.float32)
        # checkpoint each kv tile: the backward otherwise saves every
        # (cq, ck) score/prob tile — i.e. the full S^2 matrix in chunks.
        # Recomputing tiles keeps backward memory at O(S d), the flash-
        # attention profile.
        (m, l, acc), _ = jax.lax.scan(jax.checkpoint(kv_chunk), (m0, l0, a0),
                                      (jnp.arange(nk), kc, vc))
        out = acc / jnp.maximum(l, 1e-30)[..., None]         # (b,kvh,rep,cq,d)
        return None, out.transpose(0, 3, 1, 2, 4)            # (b,cq,kvh,rep,d)

    _, blocks = jax.lax.scan(q_block, None, (jnp.arange(nq), qg))
    out = blocks.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, h, d)
    return out.astype(q.dtype)


def attention(p, cfg: AttnConfig, x, *, positions=None):
    """Full-sequence attention (train / prefill). x: (B, S, d)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q, k, v = _qkv(p, cfg, x, positions)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    if cfg.impl == "flash":
        from repro.kernels.attention.ops import flash_attention
        out = flash_attention(q, k, v, causal=cfg.causal)
    elif cfg.impl == "chunked":
        out = _chunked_attn(q, k, v, causal=cfg.causal, chunk=cfg.chunk_size)
    else:
        out = _dense_attn(q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep),
                          causal=cfg.causal)
    out = shard_annotate(out, ("batch", None, "heads", None))
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype)), (k, v)


def _seq_sharded_cache_update(cache, new, length):
    """Cache write that stays LOCAL under sequence sharding.

    A plain dynamic-update-slice at a runtime index on a seq-sharded cache
    makes GSPMD fall back to "involuntary full rematerialization" — it
    replicates the whole (B, S, kvH, hd) cache per layer (observed: the
    qwen1.5-110b decode_32k cell at 20.7 GiB/chip and ~56 GB of per-step
    HBM traffic).  Here each sequence shard checks whether ``length`` falls
    in its range and writes locally via ``shard_map``; every other shard is
    a no-op.
    """
    from repro.dist.sharding import current_context

    ctx = current_context()
    mesh = ctx.mesh
    seq_ax = ctx.cache_seq_axis
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_batch = math.prod(sizes.get(a, 1) for a in ctx.data_axes)
    batch_spec = ctx.data_axes if cache.shape[0] % n_batch == 0 else None

    def local(c, n, ln):
        s_loc = c.shape[1]
        off = jax.lax.axis_index(seq_ax) * s_loc
        idx = ln - off

        def write(c):
            return jax.lax.dynamic_update_slice_in_dim(
                c, n.astype(c.dtype), jnp.clip(idx, 0, s_loc - 1), axis=1)

        return jax.lax.cond((idx >= 0) & (idx < s_loc), write, lambda c: c, c)

    P_ = P(batch_spec, seq_ax, None, None)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P_, P(batch_spec, None, None, None), P()),
                         out_specs=P_, check_vma=False)(cache, new, length)


def _update_cache(cache, new, length):
    from repro.dist.sharding import current_context
    ctx = current_context()
    if ctx.cache_seq_axis is not None and ctx.mesh is not None:
        return _seq_sharded_cache_update(cache, new, length)
    return jax.lax.dynamic_update_slice_in_dim(
        cache, new.astype(cache.dtype), length, axis=1)


def _flash_decode(q, cache_k, cache_v, k_new, v_new, cache_len, *,
                  n_rep: int, scale: float):
    """Sequence-parallel one-token decode attention via ``shard_map``.

    With the KV cache sequence-sharded (kv-heads indivisible by the model
    axis), GSPMD's pjit lowering all-gathers the full cache per layer per
    token (measured: 2 x 1.07 GB f32 gathers/layer on internlm2 decode_32k).
    Flash-decode keeps everything local: each seq shard updates its slice of
    the cache, computes local scores/max/sum/partial-out, and the softmax is
    completed with three tiny psums (max, denom, numerator).
    """
    from repro.dist.sharding import current_context

    ctx = current_context()
    mesh = ctx.mesh
    seq_ax = ctx.cache_seq_axis
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_batch = math.prod(sizes.get(a, 1) for a in ctx.data_axes)
    bspec = ctx.data_axes if q.shape[0] % n_batch == 0 else None

    def local(q, ck, cv, kn, vn, ln):
        s_loc = ck.shape[1]
        off = jax.lax.axis_index(seq_ax) * s_loc
        idx = ln - off

        def write(c_n):
            c, n = c_n
            return jax.lax.dynamic_update_slice_in_dim(
                c, n.astype(c.dtype), jnp.clip(idx, 0, s_loc - 1), axis=1)

        inb = (idx >= 0) & (idx < s_loc)
        ck = jax.lax.cond(inb, write, lambda cn: cn[0], (ck, kn))
        cv = jax.lax.cond(inb, write, lambda cn: cn[0], (cv, vn))

        # GQA-aware: never repeat the KV cache (a jnp.repeat materializes
        # h/kvh extra copies of the dominant HBM stream)
        b, _, h, d = q.shape
        kvh = ck.shape[2]
        qg = q.reshape(b, kvh, n_rep, d)
        s = jnp.einsum("bkrd,bskd->bkrs", qg, ck,
                       preferred_element_type=jnp.float32) * scale
        cols = off + jnp.arange(s_loc)
        s = jnp.where((cols <= ln)[None, None, None, :], s, NEG_INF)
        m_loc = jnp.max(s, axis=-1)                          # (b,kvh,rep)
        m = jax.lax.pmax(m_loc, seq_ax)
        pr = jnp.exp(s - m[..., None])
        denom = jax.lax.psum(jnp.sum(pr, axis=-1), seq_ax)
        num = jnp.einsum("bkrs,bskd->bkrd", pr.astype(cv.dtype), cv,
                         preferred_element_type=jnp.float32)
        num = jax.lax.psum(num, seq_ax)
        out = (num / jnp.maximum(denom, 1e-30)[..., None]).reshape(
            b, 1, h, d)
        return out.astype(q.dtype), ck, cv

    Pc = P(bspec, seq_ax, None, None)
    Pq = P(bspec, None, None, None)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(Pq, Pc, Pc, Pq, Pq, P()),
                         out_specs=(Pq, Pc, Pc),
                         check_vma=False)(q, cache_k, cache_v, k_new, v_new,
                                          cache_len)


def decode_attention(p, cfg: AttnConfig, x, cache_k, cache_v, cache_len):
    """One-token decode against a KV cache.

    x: (B, 1, d); cache_k/v: (B, S_max, kvH, hd); cache_len: () current
    length.  Returns (out (B,1,d), new_k, new_v).
    """
    from repro.dist.sharding import current_context

    b = x.shape[0]
    positions = jnp.broadcast_to(cache_len[None, None], (b, 1))
    q, k_new, v_new = _qkv(p, cfg, x, positions)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    scale = 1.0 / math.sqrt(cfg.head_dim)

    ctx = current_context()
    if ctx.cache_seq_axis is not None and ctx.mesh is not None:
        out, cache_k, cache_v = _flash_decode(
            q, cache_k, cache_v, k_new, v_new, cache_len,
            n_rep=n_rep, scale=scale)
        return (jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype)),
                cache_k, cache_v)

    cache_k = _update_cache(cache_k, k_new, cache_len)
    cache_v = _update_cache(cache_v, v_new, cache_len)
    s_max = cache_k.shape[1]
    # GQA-aware, f32 only in score/probability tiles: repeating or
    # upcasting the cache multiplies the dominant HBM stream of the step
    b_, _, h_, d_ = q.shape
    kvh = cache_k.shape[2]
    qg = q.reshape(b_, kvh, h_ // kvh, d_)
    s = jnp.einsum("bkrd,bskd->bkrs", qg, cache_k,
                   preferred_element_type=jnp.float32) * scale
    valid = (jnp.arange(s_max) <= cache_len)[None, None, None, :]
    s = jnp.where(valid, s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    # probabilities stay f32 (matching _dense_attn): rounding them to the
    # cache dtype makes decode drift from the teacher-forced logits by
    # O(1e-1) within a few steps; only the CACHE stays in the low dtype
    out = jnp.einsum("bkrs,bskd->bkrd", pr, cache_v,
                     preferred_element_type=jnp.float32)
    out = out.reshape(b_, 1, h_, d_).astype(x.dtype)
    return (jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype)),
            cache_k, cache_v)
