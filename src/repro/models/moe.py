"""Mixture-of-Experts FFN with top-k routing.

Three dispatch implementations, all sharing the same router/expert params:

* ``ref``       — dense all-experts reference (exact, no capacity drops);
  O(E * N * d * f) compute, so smoke tests / correctness only.
* ``scatter``   — global sort-based dispatch in pure pjit ops (argsort by
  expert id, capacity-bounded scatter into an (E, cap, d) buffer, grouped
  expert matmuls, scatter-combine).  GSPMD infers the communication.  This
  is the *baseline* the ECM analysis starts from.
* ``shard_map`` — explicit expert parallelism: tokens stay on their data
  shard (they are replicated across the ``model`` axis anyway), each model
  shard selects the assignments routed to its local experts, computes them,
  and the partial outputs are combined with a ``psum`` over ``model``.
  FSDP'd expert weights are all-gathered over ``data`` on entry.  This is
  the ECM-guided optimized path (see EXPERIMENTS.md §Perf).

Routing semantics are identical (same top-k, same renormalised weights);
``scatter``/``shard_map`` drop overflow beyond ``capacity_factor``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .common import ParamSpec, shard_annotate


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int
    capacity_factor: float = 1.25
    impl: str = "scatter"          # ref | scatter | shard_map
    router_dtype: object = jnp.float32


def moe_spec(d_model: int, cfg: MoEConfig) -> dict:
    e, f = cfg.n_experts, cfg.d_ff
    return {
        "router": ParamSpec((d_model, e), ("embed", "experts_r")),
        "w_gate": ParamSpec((e, d_model, f), ("experts", "embed", "mlp")),
        "w_up": ParamSpec((e, d_model, f), ("experts", "embed", "mlp")),
        "w_down": ParamSpec((e, f, d_model), ("experts", "mlp", "embed")),
    }


def _route(p, cfg: MoEConfig, xf):
    """xf: (N, d) -> (weights (N,k), ids (N,k), aux load-balance loss)."""
    logits = (xf.astype(cfg.router_dtype)
              @ p["router"].astype(cfg.router_dtype))          # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg.top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # Switch-style load-balance aux loss
    e = cfg.n_experts
    density = jnp.mean(jax.nn.one_hot(ids[:, 0], e), axis=0)
    mean_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(density * mean_probs)
    return weights.astype(xf.dtype), ids, aux


def _expert_ffn(w_gate, w_up, w_down, buf):
    """buf: (E, C, d) -> (E, C, d) through each expert's SwiGLU."""
    dt = buf.dtype
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(dt))
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(dt))


def _capacity(n_tokens: int, cfg: MoEConfig, shards: int = 1) -> int:
    cap = int(math.ceil(n_tokens * cfg.top_k * cfg.capacity_factor
                        / cfg.n_experts))
    return max(8, ((cap + 127) // 128) * 128)


# ---------------------------------------------------------------------------
# ref: dense all-experts (exact; smoke/correctness only)
# ---------------------------------------------------------------------------


def moe_ffn_ref(p, cfg: MoEConfig, x):
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    weights, ids, aux = _route(p, cfg, xf)
    out = jnp.zeros_like(xf)
    for e in range(cfg.n_experts):
        w_e = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)   # (N,)
        h = _expert_ffn(p["w_gate"][e:e + 1], p["w_up"][e:e + 1],
                        p["w_down"][e:e + 1], xf[None])
        out = out + h[0] * w_e[:, None]
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# scatter: global sort-based dispatch (pure pjit baseline)
# ---------------------------------------------------------------------------


def moe_ffn_scatter(p, cfg: MoEConfig, x):
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    k, e = cfg.top_k, cfg.n_experts
    weights, ids, aux = _route(p, cfg, xf)

    cap = _capacity(n, cfg)
    flat_ids = ids.reshape(-1)                                  # (N*k,)
    sort_idx = jnp.argsort(flat_ids)
    sorted_ids = flat_ids[sort_idx]
    token_of = sort_idx // k
    counts = jnp.zeros((e,), jnp.int32).at[flat_ids].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(n * k, dtype=jnp.int32) - starts[sorted_ids]
    valid = pos < cap
    slot = sorted_ids * cap + jnp.where(valid, pos, cap - 1)

    gathered = xf[token_of] * valid[:, None].astype(xf.dtype)
    buf = jnp.zeros((e * cap, d), xf.dtype).at[slot].add(
        gathered, mode="drop")
    buf = shard_annotate(buf.reshape(e, cap, d), ("experts", None, None))
    h = _expert_ffn(p["w_gate"], p["w_up"], p["w_down"], buf)
    h = shard_annotate(h, ("experts", None, None))

    rows = h.reshape(e * cap, d)[slot] * valid[:, None].astype(xf.dtype)
    inv = jnp.argsort(sort_idx)
    rows = rows[inv].reshape(n, k, d)
    out = jnp.sum(rows * weights[..., None], axis=1)
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# shard_map: explicit expert parallelism (ECM-optimized path)
# ---------------------------------------------------------------------------


def moe_ffn_shard_map(p, cfg: MoEConfig, x, *, mesh, data_axes=("data",),
                      model_axis="model", fsdp_axis: str | None = None):
    """Expert-parallel MoE.  Tokens are data-sharded (replicated over
    ``model``); each model shard computes only its local experts and the
    partials are psum'd over ``model``.  Dispatch never leaves the device —
    the collective cost is one psum of the (local-batch, d) output plus the
    FSDP weight all-gather, instead of GSPMD's inferred scatter traffic."""
    from jax.sharding import PartitionSpec as P

    n_model = mesh.shape[model_axis]
    e = cfg.n_experts
    assert e % n_model == 0, (e, n_model)
    e_loc = e // n_model
    k = cfg.top_k

    def local(x_loc, router, w_gate, w_up, w_down):
        # gather FSDP'd expert weights (pod-local data axis), cast to the
        # compute dtype BEFORE the gather: the wire and the gathered HBM
        # copy cost 2 B/param instead of 4 (§Perf iteration log)
        if fsdp_axis is not None and mesh.shape[fsdp_axis] > 1:
            cdt = x_loc.dtype
            w_gate = jax.lax.all_gather(w_gate.astype(cdt), fsdp_axis,
                                        axis=1, tiled=True)
            w_up = jax.lax.all_gather(w_up.astype(cdt), fsdp_axis,
                                      axis=1, tiled=True)
            w_down = jax.lax.all_gather(w_down.astype(cdt), fsdp_axis,
                                        axis=1, tiled=True)
        bl, sl, d = x_loc.shape
        xf = x_loc.reshape(-1, d)
        n = xf.shape[0]
        weights, ids, aux = _route({"router": router}, cfg, xf)
        m = jax.lax.axis_index(model_axis)
        lo = m * e_loc
        local_mask = (ids >= lo) & (ids < lo + e_loc)           # (N, k)
        loc_ids = jnp.where(local_mask, ids - lo, e_loc)        # e_loc = trash
        flat_ids = loc_ids.reshape(-1)
        cap = _capacity(n, cfg)                                  # per expert
        sort_idx = jnp.argsort(flat_ids)
        sorted_ids = flat_ids[sort_idx]
        token_of = sort_idx // k
        counts = jnp.zeros((e_loc + 1,), jnp.int32).at[flat_ids].add(1)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(n * k, dtype=jnp.int32) - starts[sorted_ids]
        valid = (pos < cap) & (sorted_ids < e_loc)
        slot = jnp.where(valid, sorted_ids * cap + pos, e_loc * cap)
        gathered = xf[token_of] * valid[:, None].astype(xf.dtype)
        buf = jnp.zeros((e_loc * cap + 1, d), xf.dtype).at[slot].add(gathered)
        h = _expert_ffn(w_gate, w_up, w_down,
                        buf[:-1].reshape(e_loc, cap, d))
        rows = h.reshape(e_loc * cap, d)
        rows = jnp.concatenate([rows, jnp.zeros((1, d), rows.dtype)], 0)[slot]
        w_sorted = (weights * local_mask.astype(weights.dtype)).reshape(-1)[sort_idx]
        contrib = rows * w_sorted[:, None]
        out = jnp.zeros((n, d), xf.dtype).at[token_of].add(contrib)
        out = jax.lax.psum(out, model_axis)
        aux = jax.lax.pmean(aux, (*data_axes, model_axis))
        return out.reshape(bl, sl, d), aux

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(data_axes, None, None),
                  P(None, None),
                  P(model_axis, fsdp_axis, None),
                  P(model_axis, fsdp_axis, None),
                  P(model_axis, fsdp_axis, None)),
        out_specs=(P(data_axes, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def moe_ffn(p, cfg: MoEConfig, x, *, mesh=None, data_axes=("data",),
            model_axis="model", fsdp_axis=None):
    if cfg.impl == "ref":
        return moe_ffn_ref(p, cfg, x)
    if cfg.impl == "shard_map":
        assert mesh is not None, "shard_map MoE needs a mesh"
        return moe_ffn_shard_map(p, cfg, x, mesh=mesh, data_axes=data_axes,
                                 model_axis=model_axis, fsdp_axis=fsdp_axis)
    return moe_ffn_scatter(p, cfg, x)
