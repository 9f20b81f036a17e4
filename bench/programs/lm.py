"""The program under test for the LM configurations: its ``ArchDef`` with
a configuration file's fields, and the reference's weights in the
program's parameter layout."""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from bench.reference import lm as ref

#: configuration keys that are fields of the program's ``LMConfig``
LM_FIELDS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
             "d_ff", "vocab", "qkv_bias", "rope_fraction", "rope_theta",
             "norm_eps")


def build_arch(cfg: dict):
    """The registry's architecture with the configuration's fields."""
    from repro.configs import get_arch

    arch = get_arch(cfg["registry"], smoke=False)
    lm_cfg = dataclasses.replace(arch.cfg, **{k: cfg[k] for k in LM_FIELDS})
    return dataclasses.replace(arch, cfg=lm_cfg)


def model_cfg(cfg: dict, arch) -> dict:
    """What the reference and the counts read: the configuration plus the
    embedding tables' rows as the program holds them."""
    return {**{k: cfg[k] for k in ref.MODEL_KEYS if k in cfg},
            "vocab_padded": arch.cfg.vocab_padded}


def _program_layout(mcfg: dict, key) -> dict:
    layers = jax.vmap(lambda i: ref.layer_weights(mcfg, key, i))(
        jnp.arange(mcfg["n_layers"]))
    g = ref.global_weights(mcfg, key)
    attn = {k: layers[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in layers}
    return {
        "embedding": g["embedding"],
        "layers": {"attn": attn, "ln_attn": layers["ln_attn"],
                   "ln_ffn": layers["ln_ffn"],
                   "mlp": {k: layers[k] for k in ("w_gate", "w_up",
                                                  "w_down")}},
        "ln_f": g["ln_f"],
        "unembed": g["unembed"],
    }


def make_params(arch, mcfg: dict, seed: int, shardings):
    """The reference's weights from ``seed``, in the program's layout and
    dtype, made on the device in one jitted call."""
    spec = jax.eval_shape(partial(_program_layout, mcfg), ref.seed_key(0))
    def sig(s):
        return tuple(s.shape), jnp.dtype(s.dtype)

    want = jax.tree.map(sig, arch.param_spec(),
                        is_leaf=lambda x: hasattr(x, "init"))
    got = jax.tree.map(sig, spec)
    if got != want:
        raise ValueError("the program's parameter layout changed: "
                         f"{want} != {got}")
    return jax.jit(partial(_program_layout, mcfg),
                   out_shardings=shardings)(ref.seed_key(seed))
