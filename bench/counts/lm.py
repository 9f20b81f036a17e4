"""Operations and bytes of a decoder-only LM step, from the config's shapes.

These are the counts the step's roofline share divides by: the least work
any correct implementation must do, not what the program happens to do.

- FLOPs: 2 per multiply-add of every projection and MLP matmul; attention
  as half of the full causal score matrix (QK^T and PV); the output head
  only where logits are produced.  Recomputation is not counted.
- Bytes: every weight read once per call at 2 bytes (bf16, the compute
  dtype); the K/V cache at 2 bytes, live positions only.  Activations are
  not counted.

``cfg`` is a dict with the keys of the configuration files under
``bench/configs/``: ``n_layers``, ``d_model``, ``n_heads``, ``n_kv_heads``,
``head_dim``, ``d_ff``, ``vocab``.
"""
from __future__ import annotations

WEIGHT_BYTES = 2
KV_BYTES = 2


def layer_matmul_params(cfg: dict) -> int:
    """Projection and MLP weights of one layer (biases and norms excluded)."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * cfg["d_ff"]


def matmul_params(cfg: dict) -> int:
    """Matmul weights of all layers."""
    return cfg["n_layers"] * layer_matmul_params(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """K and V of one position over all layers."""
    return (2 * cfg["n_layers"] * cfg["n_kv_heads"] * cfg["head_dim"]
            * KV_BYTES)


def _attn_flops(cfg: dict, q_tokens: int, keys: float) -> float:
    """QK^T and PV for ``q_tokens`` queries against ``keys`` keys each."""
    return (4.0 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"]
            * q_tokens * keys)


def _head_flops(cfg: dict, rows: int) -> float:
    return 2.0 * cfg["d_model"] * cfg["vocab"] * rows


def _weight_bytes(cfg: dict) -> float:
    """Layer weights plus the output head, read once."""
    return WEIGHT_BYTES * (matmul_params(cfg) + cfg["d_model"] * cfg["vocab"])


def prefill(cfg: dict, batch: int, prompt: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill of ``batch`` prompts of ``prompt``
    tokens, producing last-position logits and writing the K/V cache."""
    tokens = batch * prompt
    flops = (2.0 * matmul_params(cfg) * tokens
             + _attn_flops(cfg, tokens, (prompt + 1) / 2)
             + _head_flops(cfg, batch))
    nbytes = (_weight_bytes(cfg) + tokens * kv_bytes_per_token(cfg)
              + WEIGHT_BYTES * cfg["d_model"] * tokens)
    return flops, nbytes


def decode_step(cfg: dict, batch: int, context: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step: ``batch`` new tokens, each at
    position ``context`` (so ``context + 1`` live K/V positions)."""
    live = context + 1
    flops = (2.0 * matmul_params(cfg) * batch
             + _attn_flops(cfg, batch, live) + _head_flops(cfg, batch))
    nbytes = (_weight_bytes(cfg) + batch * live * kv_bytes_per_token(cfg)
              + WEIGHT_BYTES * cfg["d_model"] * batch)
    return flops, nbytes


def decode(cfg: dict, batch: int, prompt: int, steps: int
           ) -> tuple[float, float]:
    """(FLOPs, bytes) of ``steps`` decode steps after a ``prompt``-token
    prefill."""
    parts = [decode_step(cfg, batch, prompt + i) for i in range(steps)]
    return sum(p[0] for p in parts), sum(p[1] for p in parts)


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time at the chip's peaks: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
