"""Shared model infrastructure: parameter specs, logical-axis sharding,
norms, RoPE, MLPs, embeddings and the LM loss.

Parameters are declared as trees of :class:`ParamSpec` (shape + logical axis
names + initializer).  The same spec tree materialises into (a) actual
arrays for smoke tests / examples, (b) ``ShapeDtypeStruct`` stand-ins for
the dry-run, and (c) ``PartitionSpec`` trees via the mesh's logical-axis
rules (``repro.dist.sharding``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParamSpec:
    """Declarative parameter: shape, logical axes, initializer."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"          # normal | zeros | ones | scaled
    scale: float | None = None    # stddev override
    dtype: Any = jnp.float32

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _init_array(key, spec: ParamSpec, dtype=None):
    dtype = dtype or spec.dtype
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    std = spec.scale
    if std is None:
        # fan-in scaled normal over the last-but-one dim by convention
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, spec.shape) * std).astype(dtype)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def materialize(spec_tree, key, dtype=None):
    """Spec tree -> array tree (deterministic per-leaf keys)."""
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    arrs = [_init_array(k, s, dtype) for k, s in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, arrs)


def abstract(spec_tree, dtype=None):
    """Spec tree -> ShapeDtypeStruct tree (dry-run: no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype or s.dtype),
        spec_tree, is_leaf=is_spec)


def axes_tree(spec_tree):
    """Spec tree -> logical-axes tree (same structure, tuples as leaves)."""
    return jax.tree.map(lambda s: s.axes, spec_tree, is_leaf=is_spec)


def count_params(spec_tree) -> int:
    leaves = jax.tree.leaves(spec_tree, is_leaf=is_spec)
    return sum(int(math.prod(s.shape)) for s in leaves)


# ---------------------------------------------------------------------------
# Logical-axis activation annotation (rules installed by repro.dist)
# ---------------------------------------------------------------------------

_ACTIVATION_RULES: dict[str, Any] | None = None


def set_activation_rules(rules: dict[str, Any] | None):
    global _ACTIVATION_RULES
    _ACTIVATION_RULES = rules


def shard_annotate(x, axes: tuple[str | None, ...]):
    """Attach a sharding constraint if logical rules are installed.

    Divisibility-aware: an axis whose dimension does not divide by the mesh
    axes it maps to is left unsharded — uneven shardings make GSPMD pad and
    replicate (observed: 24 q-heads annotated onto a 16-way axis cost GiBs
    of padded full-size copies in the minitron-4b dry-run).  Resolution is
    ``dist.sharding.logical_to_pspec``'s.  The annotation is skipped when
    the rules name an axis the active mesh lacks; any other error raises.
    """
    if _ACTIVATION_RULES is None:
        return x
    from repro.dist.sharding import current_mesh, logical_to_pspec

    mesh = current_mesh()
    if mesh is None:
        return x
    pspec = logical_to_pspec(axes, _ACTIVATION_RULES, x.shape, mesh)
    named = {g for e in pspec if e is not None
             for g in (e if isinstance(e, tuple) else (e,))}
    if not named <= set(mesh.axis_names):
        return x
    return jax.lax.with_sharding_constraint(x, pspec)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_spec(d: int) -> ParamSpec:
    return ParamSpec((d,), ("embed",), init="ones")


def rmsnorm(w, x, eps: float = 1e-6):
    """RMSNorm with f32 *statistics* but no materialized f32 copy of x.

    The sum-of-squares accumulates in f32 (``preferred_element_type``); the
    per-row rsqrt scale is applied in the compute dtype.  Keeping the
    (B, S, d) tensor out of f32 matters structurally: a full ``x.astype
    (f32)`` inside a scanned layer makes XLA save/convert the whole
    per-layer carry stack in f32 in the backward pass (2x the remat
    memory, observed on the dry-run).
    """
    dt = x.dtype
    ss = jnp.einsum("...d,...d->...", x, x,
                    preferred_element_type=jnp.float32)[..., None]
    var = ss / x.shape[-1]
    scale = jax.lax.rsqrt(var + eps).astype(dt)
    return w.astype(dt) * (x * scale)


def layernorm_spec(d: int) -> dict:
    return {"scale": ParamSpec((d,), ("embed",), init="ones"),
            "bias": ParamSpec((d,), ("embed",), init="zeros")}


def layernorm(p, x, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return (p["scale"] * (xf - mu) * jax.lax.rsqrt(var + eps)
            + p["bias"]).astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings (with partial-dim support for GLM4)
# ---------------------------------------------------------------------------


def rope_angles(positions, head_dim: int, *, theta: float = 10000.0,
                fraction: float = 1.0):
    """Return (cos, sin) of shape (..., rot_dim/2) for given positions."""
    rot = int(head_dim * fraction)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions[..., None].astype(jnp.float32) * inv      # (..., rot/2)
    return jnp.cos(ang), jnp.sin(ang), rot


def apply_rope(x, cos, sin, rot: int):
    """x: (B, S, H, D); rotate the first ``rot`` dims pairwise."""
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    r1 = x1 * c - x2 * s
    r2 = x2 * c + x1 * s
    xr = jnp.stack([r1, r2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([xr, xp], axis=-1) if rot < x.shape[-1] else xr


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def swiglu_spec(d: int, d_ff: int) -> dict:
    return {
        "w_gate": ParamSpec((d, d_ff), ("embed", "mlp")),
        "w_up": ParamSpec((d, d_ff), ("embed", "mlp")),
        "w_down": ParamSpec((d_ff, d), ("mlp", "embed")),
    }


def swiglu(p, x):
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
    h = jax.nn.silu(g) * u
    h = shard_annotate(h, ("batch", None, "mlp"))
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))


def gelu_mlp_spec(d: int, d_ff: int) -> dict:
    return {
        "w_in": ParamSpec((d, d_ff), ("embed", "mlp")),
        "b_in": ParamSpec((d_ff,), ("mlp",), init="zeros"),
        "w_out": ParamSpec((d_ff, d), ("mlp", "embed")),
        "b_out": ParamSpec((d,), ("embed",), init="zeros"),
    }


def gelu_mlp(p, x):
    h = jnp.einsum("bsd,df->bsf", x, p["w_in"].astype(x.dtype)) + p["b_in"].astype(x.dtype)
    h = jax.nn.gelu(h)
    h = shard_annotate(h, ("batch", None, "mlp"))
    return jnp.einsum("bsf,fd->bsd", h, p["w_out"].astype(x.dtype)) + p["b_out"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding / loss
# ---------------------------------------------------------------------------


def embedding_spec(vocab: int, d: int) -> ParamSpec:
    return ParamSpec((vocab, d), ("vocab", "embed"), scale=1.0)


def embed(table, tokens):
    return jnp.take(table, tokens, axis=0)


def unembed_spec(d: int, vocab: int) -> ParamSpec:
    return ParamSpec((d, vocab), ("embed", "vocab"))


def unembed(w, x):
    return jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))


def masked_xent(logits, labels, mask=None, *, vocab: int,
                vocab_padded: int | None = None, z_loss: float = 0.0):
    """Stable masked cross entropy with padded-vocab masking (f32 math)."""
    vpad = vocab_padded or vocab
    lf = logits.astype(jnp.float32)
    if vpad != vocab:
        pad_mask = jnp.arange(vpad) >= vocab
        lf = jnp.where(pad_mask[None, None, :], jnp.asarray(-1e30, jnp.float32), lf)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None].astype(jnp.int32),
                             axis=-1)[..., 0]
    per_tok = lse - ll
    if z_loss:
        per_tok = per_tok + z_loss * lse**2
    if mask is None:
        return jnp.mean(per_tok)
    maskf = mask.astype(jnp.float32)
    return jnp.sum(per_tok * maskf) / jnp.maximum(jnp.sum(maskf), 1.0)


def softmax_xent(logits, labels, *, z_loss: float = 0.0):
    """Stable per-token cross entropy, mean over tokens (f32 math).

    ``z_loss`` adds the standard log-normalizer regulariser (used at scale
    to keep logits bounded)."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    loss = lse - ll
    if z_loss:
        loss = loss + z_loss * lse**2
    return jnp.mean(loss)
