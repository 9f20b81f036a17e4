"""Architecture definitions: the uniform API every assigned arch implements.

An :class:`ArchDef` binds a model family's functions (spec / loss / prefill /
decode / cache-spec) to one concrete configuration, and knows how to build
its inputs for each assigned input shape — as numpy arrays (smoke tests,
examples) or as ``ParamSpec`` trees (the dry-run's ShapeDtypeStruct
stand-ins, which double as the source of input shardings).

Input shapes (assigned, global):

=============  ========  ============  =======================
shape          seq_len   global_batch  lowers
=============  ========  ============  =======================
train_4k       4,096     256           ``train_step``
prefill_32k    32,768    32            ``prefill_step``
decode_32k     32,768    128           ``serve_step`` (1 token)
long_500k      524,288   1             ``serve_step`` (1 token)
=============  ========  ============  =======================

``long_500k`` requires sub-quadratic sequence mixing and is skipped (with a
recorded reason) for pure full-attention architectures, per the brief.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import ParamSpec, abstract, count_params, is_spec


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # train | prefill | decode

    @property
    def tokens_per_step(self) -> int:
        return self.seq_len * self.global_batch


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# ArchDef
# ---------------------------------------------------------------------------


def stored_params(params, cfg):
    """The default ``serving_params_fn``: serve the parameters as stored."""
    return params


@dataclass(frozen=True)
class ArchDef:
    """One selectable architecture (``--arch <name>``)."""

    name: str
    family: str                    # dense | moe | hybrid | ssm | vlm | audio
    cfg: Any                       # model config dataclass
    spec_fn: Callable
    loss_fn: Callable
    prefill_fn: Callable
    decode_fn: Callable
    cache_spec_fn: Callable
    profile: str = "tp_dp"         # sharding profile (repro.dist.sharding)
    sub_quadratic: bool = False    # may run long_500k
    has_decoder: bool = True       # encoder-only archs skip decode shapes
    source: str = ""               # provenance note ([arXiv/hf; tier])
    #: extra per-shape batch entries: name -> fn(shape, cfg) -> ParamSpec
    extra_inputs: dict = field(default_factory=dict)
    #: full override of batch_spec: fn(shape, cfg) -> dict[str, ParamSpec]
    batch_spec_fn: Callable | None = None
    #: gradient-accumulation microbatches for train_4k (memory-term knob:
    #: global batch preserved, per-device live activations divided)
    train_accum: int = 1
    #: Adam moment storage for the production config (f32 | bf16 | int8);
    #: the HBM-footprint knob for the very large archs
    moment_dtype: str = "f32"
    #: fn(params, cfg) -> the tree the serve path's prefill and decode
    #: read, made once per ``generate`` call: the family's leaves that its
    #: code casts to the compute dtype at every use, cast once
    serving_params_fn: Callable = stored_params

    # -- parameters ----------------------------------------------------
    def param_spec(self):
        return self.spec_fn(self.cfg)

    @property
    def n_params(self) -> int:
        return count_params(self.param_spec())

    @property
    def n_active_params(self) -> int:
        """Active params per token (MoE: experts scaled by top_k/n_experts)."""
        spec = self.param_spec()
        moe = getattr(self.cfg, "moe", None)
        if moe is None:
            return count_params(spec)
        total = 0
        flat, _ = jax.tree_util.tree_flatten_with_path(
            spec, is_leaf=is_spec)
        for path, s in flat:
            n = int(math.prod(s.shape))
            if "experts" in s.axes:     # expert-parallel weights
                n = int(n * moe.top_k / moe.n_experts)
            total += n
        return total

    # -- model fns -----------------------------------------------------
    def loss(self, params, batch):
        return self.loss_fn(params, self.cfg, batch)

    def prefill(self, params, batch, *, max_len: int | None = None):
        return self.prefill_fn(params, self.cfg, batch, max_len=max_len)

    def decode(self, params, cache, batch):
        return self.decode_fn(params, self.cfg, cache, batch)

    def serving_params(self, params):
        return self.serving_params_fn(params, self.cfg)

    def cache_spec(self, batch_size: int, max_len: int):
        return self.cache_spec_fn(self.cfg, batch_size, max_len)

    # -- shape policy ----------------------------------------------------
    def shape_supported(self, shape: ShapeSpec) -> tuple[bool, str]:
        if shape.kind == "decode" and not self.has_decoder:
            return False, "encoder-only: no decode step"
        if shape.name == "long_500k" and not self.sub_quadratic:
            return False, "full-attention arch: long_500k needs sub-quadratic mixing"
        return True, ""

    def cells(self) -> list[tuple[ShapeSpec, bool, str]]:
        return [(s, *self.shape_supported(s)) for s in SHAPES.values()]

    # -- inputs ----------------------------------------------------------
    def batch_spec(self, shape: ShapeSpec) -> dict:
        """ParamSpec tree of the step's *data* inputs (not params/cache)."""
        if self.batch_spec_fn is not None:
            return self.batch_spec_fn(shape, self.cfg)
        b = shape.global_batch
        s = shape.seq_len if shape.kind != "decode" else 1
        text_s = self._text_len(shape, s)
        out = {
            "tokens": ParamSpec((b, text_s), ("batch", None), init="zeros",
                                dtype=jnp.int32),
        }
        if shape.kind == "train":
            out["labels"] = ParamSpec((b, self._label_len(shape, text_s)),
                                      ("batch", None), init="zeros",
                                      dtype=jnp.int32)
            out["mask"] = ParamSpec((b, self._label_len(shape, text_s)),
                                    ("batch", None), init="ones",
                                    dtype=jnp.float32)
        for k, fn in self.extra_inputs.items():
            spec = fn(shape, self.cfg)
            if spec is not None:
                out[k] = spec
        return out

    def _text_len(self, shape: ShapeSpec, s: int) -> int:
        """Token-stream length (VLM archs reserve prefix positions)."""
        prefix = getattr(self.cfg, "image_prefix", 0)
        if shape.kind == "decode":
            return 1
        return max(s - prefix, 1)

    def _label_len(self, shape: ShapeSpec, text_s: int) -> int:
        prefix = getattr(self.cfg, "image_prefix", 0)
        return text_s + prefix

    def abstract_batch(self, shape: ShapeSpec):
        return abstract(self.batch_spec(shape))

    def make_batch(self, shape: ShapeSpec, seed: int = 0) -> dict:
        """Concrete numpy batch for this shape (smoke/example scale only)."""
        g = np.random.Generator(np.random.Philox(key=[seed, 7]))
        out = {}
        for k, spec in self.batch_spec(shape).items():
            if spec.dtype == jnp.int32:
                vocab = getattr(self.cfg, "vocab", 1024)
                out[k] = g.integers(0, vocab, size=spec.shape).astype(np.int32)
            elif spec.init == "ones":
                out[k] = np.ones(spec.shape, np.float32)
            else:
                out[k] = g.standard_normal(spec.shape).astype(np.float32) * 0.02
        return out

    # -- useful-work accounting (§Roofline) -------------------------------
    def model_flops(self, shape: ShapeSpec) -> float:
        """MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference fwd), N = active."""
        n = self.n_active_params
        if shape.kind == "train":
            return 6.0 * n * shape.tokens_per_step
        if shape.kind == "prefill":
            return 2.0 * n * shape.tokens_per_step
        return 2.0 * n * shape.global_batch          # decode: 1 token/seq
