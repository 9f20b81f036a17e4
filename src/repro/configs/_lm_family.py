"""Shared constructor for dense/MoE decoder-only LM architectures."""
from __future__ import annotations

from repro.models import lm
from .base import ArchDef


def lm_arch(name: str, cfg: lm.LMConfig, *, family: str = "dense",
            profile: str = "tp_dp", source: str = "",
            extra_inputs: dict | None = None,
            batch_spec_fn=None, train_accum: int = 1,
            moment_dtype: str = "f32") -> ArchDef:
    return ArchDef(
        name=name,
        family=family,
        cfg=cfg,
        spec_fn=lm.lm_spec,
        loss_fn=lm.loss_fn,
        prefill_fn=lm.prefill,
        decode_fn=lm.decode_step,
        cache_spec_fn=lm.cache_spec,
        profile=profile,
        sub_quadratic=False,
        source=source,
        extra_inputs=extra_inputs or {},
        batch_spec_fn=batch_spec_fn,
        train_accum=train_accum,
        moment_dtype=moment_dtype,
        serving_params_fn=lm.serving_params,
    )
