#!/usr/bin/env python3
"""Drive the main path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: serve + kernels
    python chip_smoke.py --chips 4   # four chips: sharded training only

One chip, phase *serve*: internlm2-1.8b at its published widths, random
weights from ``--seed``, answers 4 requests of 512 prompt tokens with 16
greedily decoded tokens each, through the same ``generate`` path as
``python -m repro.launch.serve --no-smoke``.  Every logit must be finite,
and each decode step's logits must match a fresh prefill over the prompt
plus the tokens generated so far.

One chip, phase *kernels*: the paper's Table I stream kernels at 2^26 f32
elements per stream, at ``num_stages`` None/1/2/3, against
``kernels/stream/ref.py``; then jacobi2d at 8192^2, matmul at 4096^3 bf16
and flash attention at the serve shape, each against its ``ref.py``.

Four chips: three training steps of internlm2-1.8b at published widths
through the launcher's ``Trainer``, its parameters and Adam state split
over a (data=1, model=4) mesh.  Every chip must hold a share of the state,
and the step-0 loss must match the loss of the same seeded parameters in
a forward pass on one chip.

Everything runs in this one process.  Without a TPU it exits 1 before any
work.  A failed check exits 1; an exception (a Mosaic error among them)
ends the run.  On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Earlier lines give each phase's wall time, measured after a warm-up call
and ended by ``block_until_ready``.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.configs.base import ShapeSpec  # noqa: E402
from repro.dist.sharding import get_profile, use_mesh_context  # noqa: E402
from repro.kernels.attention import ops as attn_ops  # noqa: E402
from repro.kernels.attention import ref as attn_ref  # noqa: E402
from repro.kernels.matmul import ops as mm_ops  # noqa: E402
from repro.kernels.matmul import ref as mm_ref  # noqa: E402
from repro.kernels.stencil import ops as stencil_ops  # noqa: E402
from repro.kernels.stencil import ref as stencil_ref  # noqa: E402
from repro.kernels.stream import ops as stream_ops  # noqa: E402
from repro.kernels.stream import ref as stream_ref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.serve import generate, init_params, serve_steps  # noqa: E402
from repro.launch.train import build_trainer  # noqa: E402
from repro.models.common import materialize  # noqa: E402

ARCH = "internlm2-1.8b"
STAGES = (None, 1, 2, 3)

#: decode vs fresh prefill, per step: max|d - p| <= DECODE_TOL * max|p|.
#: Both paths keep activations in bf16 but round them at different points
#: (the prefill's chunked attention vs the decode's attention over the
#: bf16 KV cache, and matmuls of other shapes): about 10 roundings of
#: 2^-9 relative in each of 24 layers, which add up like a random walk to
#: sqrt(240) * 2^-9 ~ 3e-2 of the residual stream; the bound leaves twice
#: that.  A wrong cache position or mask moves the logits by their own
#: size, so it still fails.
DECODE_TOL = 2.0**-4
#: load/ddot: f32 sums of 2^26 elements in another order (per-lane and
#: chunk-sequential in the kernel, a tree in XLA); with positive data the
#: sequential part costs about sqrt(8192 chunks) * 2^-24 ~ 5e-6 relative.
REDUCE_RTOL = 1e-4
#: matmul: the same f32 sums of exact bf16 products, accumulated in another
#: order, may round to neighbouring bf16 values: one bf16 ulp (2^-7
#: relative) of the largest output.
MATMUL_TOL = 2.0**-7
#: flash attention: as matmul, plus XLA's default-precision f32 dot in the
#: reference rounds the softmax probabilities to bf16 (2^-9 each).
ATTN_TOL = 2.0**-6
#: four-chip step-0 loss vs one-chip forward: bf16 activations whose
#: tensor-parallel partial sums are reduced across chips in another order
#: (3e-4 relative at smoke width on 4 virtual CPU devices).
LOSS_RTOL = 1e-2

#: HBM bytes per element of each Table I kernel (4-byte f32 streams)
STREAMS = {"load": 1, "ddot": 2, "store": 1, "update": 2, "copy": 2,
           "striad": 3, "schoenauer": 4}


class Checks:
    """Collects check results; a run passes only if every check does."""

    def __init__(self):
        self.failed: list[str] = []

    def expect(self, ok: bool, what: str) -> None:
        print(f"  {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)


def _timed(fn, reps: int) -> tuple[object, float]:
    """Warm up ``fn`` once (compiling it), then return its result and the
    mean wall seconds of ``reps`` more calls."""
    out = jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(fn())
    return out, (time.perf_counter() - t0) / reps


def _normwise_err(out, want) -> float:
    """max|out - want| / max|want|, in f32."""
    out = jnp.asarray(out, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(out - want)) / jnp.max(jnp.abs(want)))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_serve(checks: Checks, *, smoke: bool = False, batch: int = 4,
                prompt_len: int = 512, gen: int = 16, seed: int = 0) -> None:
    """Serve ``batch`` requests and check them against fresh prefills."""
    arch = get_arch(ARCH, smoke=smoke)
    mesh = make_host_mesh(model=1)
    profile = get_profile(arch.profile)
    max_len = prompt_len + gen + 8          # as python -m repro.launch.serve
    shape = ShapeSpec("smoke_prefill", seq_len=prompt_len,
                      global_batch=batch, kind="prefill")
    prompt = arch.make_batch(shape, seed=seed)["tokens"]
    vocab = arch.cfg.vocab
    print(f"serve: {arch.name} d_model={arch.cfg.d_model} "
          f"layers={arch.cfg.n_layers} vocab={vocab}, {batch} requests x "
          f"{prompt_len} prompt tokens, {gen} decoded each", flush=True)

    with use_mesh_context(mesh, profile):
        t0 = time.perf_counter()
        params = jax.block_until_ready(init_params(arch, mesh, profile, seed))
        print(f"  init params: {time.perf_counter() - t0} s", flush=True)
        steps = serve_steps(arch, max_len)
        t0 = time.perf_counter()
        generate(arch, steps, params, {"tokens": jnp.asarray(prompt)}, gen)
        print(f"  warm-up (compiles): {time.perf_counter() - t0} s",
              flush=True)
        out = generate(arch, steps, params, {"tokens": jnp.asarray(prompt)},
                       gen)
        print(f"  prefill: {out['prefill_s']} s "
              f"({batch * prompt_len / out['prefill_s']} tokens/s)")
        print(f"  decode: {out['decode_s'] / gen} s/step "
              f"({batch * gen / out['decode_s']} tokens/s)", flush=True)

        finite = all(bool(jnp.all(jnp.isfinite(lg))) for lg in out["logits"])
        checks.expect(finite, f"all {gen + 1} steps' logits are finite")

        fresh = jax.jit(lambda p, b: arch.prefill(p, b))
        errs = []
        for i in range(1, gen + 1):
            seq = np.concatenate([prompt, out["tokens"][:, :i]], axis=1)
            want, _ = fresh(params, {"tokens": jnp.asarray(seq)})
            errs.append(_normwise_err(out["logits"][i][:, 0, :vocab],
                                      want[:, 0, :vocab]))
        checks.expect(max(errs) <= DECODE_TOL,
                      f"decode steps 1..{gen} match fresh prefills: "
                      f"normwise errors {errs} <= {DECODE_TOL}")


def phase_kernels(checks: Checks, *, n: int = 1 << 26, grid: int = 8192,
                  mm: int = 4096, attn_shape=(4, 512, 16, 8, 128),
                  interpret: bool = False, reps: int = 10,
                  seed: int = 0) -> None:
    """Table I stream kernels, jacobi2d, matmul and flash attention, each
    against its ``ref.py``."""
    kind = jax.devices()[0].device_kind
    keys = jax.random.split(jax.random.key(seed), 8)
    b, c, d = (jax.random.uniform(k, (n,), jnp.float32) for k in keys[:3])
    s = 1.7
    S, R = stream_ops, stream_ref
    cases = {
        "load": (lambda **kw: S.load(b, **kw), lambda: R.load(b)),
        "ddot": (lambda **kw: S.ddot(b, c, **kw), lambda: R.ddot(b, c)),
        "store": (lambda **kw: S.store(s, (n,), jnp.float32, **kw),
                  lambda: R.store(s, (n,), jnp.float32)),
        "update": (lambda **kw: S.update(s, b, **kw),
                   lambda: R.update(s, b)),
        "copy": (lambda **kw: S.copy(b, **kw), lambda: R.copy(b)),
        "striad": (lambda **kw: S.striad(s, b, c, **kw),
                   lambda: R.striad(s, b, c)),
        "schoenauer": (lambda **kw: S.schoenauer(b, c, d, **kw),
                       lambda: R.schoenauer(b, c, d)),
    }
    print(f"kernels: Table I streams at {n} f32 elements each, on {kind}",
          flush=True)
    for name, (op, ref) in cases.items():
        want = ref()
        for ns in STAGES:
            out, t = _timed(
                lambda: op(num_stages=ns, interpret=interpret), reps)
            gbs = STREAMS[name] * n * 4 / t / 1e9
            if name in ("load", "ddot"):
                ok = bool(jnp.abs(out - want) <= REDUCE_RTOL * jnp.abs(want))
                what = f"within rtol {REDUCE_RTOL}"
            elif interpret:
                # the CPU backend may fuse b + s*c into one FMA, the eager
                # reference does not (tests/test_pipeline.py's tolerance)
                ok = bool(jnp.allclose(out, want, rtol=1e-6, atol=1e-6))
                what = "within 1e-6 (interpret mode)"
            else:
                ok = bool(jnp.array_equal(out, want))
                what = "exact"
            checks.expect(ok, f"{name} num_stages={ns}: {what}; {t} s, "
                          f"{gbs} GB/s on {kind}")

    a = jax.random.normal(keys[3], (grid, grid), jnp.float32)
    want = stencil_ref.jacobi2d(a)
    for ns in STAGES:
        out, t = _timed(lambda: stencil_ops.jacobi2d(
            a, num_stages=ns, interpret=interpret), reps)
        checks.expect(bool(jnp.array_equal(out, want)),
                      f"jacobi2d {grid}^2 num_stages={ns}: exact; {t} s")

    x = jax.random.normal(keys[4], (mm, mm), jnp.bfloat16)
    y = jax.random.normal(keys[5], (mm, mm), jnp.bfloat16)
    out, t = _timed(lambda: mm_ops.matmul(x, y, interpret=interpret), reps)
    err = _normwise_err(out, mm_ref.matmul(x, y))
    checks.expect(err <= MATMUL_TOL, f"matmul {mm}^3 bf16: normwise error "
                  f"{err} <= {MATMUL_TOL}; {t} s")

    bsz, sq, h, hkv, hd = attn_shape
    q = jax.random.normal(keys[6], (bsz, sq, h, hd), jnp.bfloat16)
    k, v = (jax.random.normal(kk, (bsz, sq, hkv, hd), jnp.bfloat16)
            for kk in jax.random.split(keys[7]))
    out, t = _timed(lambda: attn_ops.flash_attention(
        q, k, v, causal=True, interpret=interpret), reps)

    def fused(x):          # (B, S, H, d) -> (B*H, S, d), KV heads repeated
        x = jnp.repeat(x, h // x.shape[2], axis=2)
        return x.transpose(0, 2, 1, 3).reshape(bsz * h, sq, hd)

    want = attn_ref.attention(fused(q), fused(k), fused(v), causal=True)
    err = _normwise_err(fused(out), want)
    checks.expect(err <= ATTN_TOL, f"flash_attention B={bsz} S={sq} H={h} "
                  f"KV={hkv} d={hd} bf16: normwise error {err} <= "
                  f"{ATTN_TOL}; {t} s")


def phase_train(checks: Checks, *, smoke: bool = False, steps: int = 3,
                batch: int = 8, seq: int = 512, model_axis: int = 4,
                seed: int = 0) -> None:
    """Sharded training steps vs a one-chip forward pass."""
    arch = get_arch(ARCH, smoke=smoke)
    n_dev = len(jax.devices())
    print(f"train: {arch.name} d_model={arch.cfg.d_model} layers="
          f"{arch.cfg.n_layers}, batch {batch} x {seq} tokens, {steps} "
          f"steps on a data={n_dev // model_axis} x model={model_axis} "
          f"mesh", flush=True)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        trainer, data = build_trainer(
            arch, steps=steps, batch=batch, seq=seq, lr=3e-3,
            model_axis=model_axis, ckpt_dir=ckpt_dir,
            ckpt_interval=steps + 1, seed=seed)

        # the same seeded parameters, whole on one chip: the step-0 loss
        one_chip = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        params = jax.jit(lambda k: materialize(arch.param_spec(), k),
                         out_shardings=one_chip)(jax.random.key(seed))
        batch0 = jax.device_put(data.batch(0), one_chip)
        ref_loss = float(jax.jit(lambda p, bt: arch.loss(p, bt)[0])(
            params, batch0))
        del params, batch0

        shares: dict[int, int] = {}
        logical = []

        def record_shares(_trainer, _step, state):
            for leaf in jax.tree.leaves(state):
                logical.append(leaf.nbytes)
                for sh in leaf.addressable_shards:
                    shares[sh.device.id] = (shares.get(sh.device.id, 0)
                                            + sh.data.nbytes)

        trainer.hooks[0] = record_shares
        t0 = time.perf_counter()
        out = trainer.run()
        wall = time.perf_counter() - t0
    steps_s = [e.wall_s for e in trainer.events]
    print(f"  run (step 0 compiles): {wall} s; step wall times {steps_s} s",
          flush=True)
    total = sum(logical)
    print(f"  state: {total / 1e9} GB; per device: " + ", ".join(
        f"{dev}: {nb / 1e9} GB" for dev, nb in sorted(shares.items())))
    # split, not replicated and not all on one chip: every device holds
    # some of it and none holds half
    checks.expect(
        len(shares) == n_dev
        and (n_dev == 1 or max(shares.values()) <= total / 2),
        f"each of {n_dev} devices holds a share of the training state")
    loss0 = out["losses"][0]
    checks.expect(abs(loss0 - ref_loss) <= LOSS_RTOL * abs(ref_loss),
                  f"step-0 loss {loss0} vs one-chip forward {ref_loss} "
                  f"(rtol {LOSS_RTOL})")
    losses = out["losses"]
    checks.expect(all(np.isfinite(losses)), f"losses finite: {losses}")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the main path once on a TPU and check it.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve + kernels on one chip; 4: sharded "
                         "training over four chips, and nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1

    cache_dir = use_compile_cache()
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw):
        for k in cache_events:
            if event == f"/jax/compilation_cache/cache_{k}":
                cache_events[k] += 1

    jax.monitoring.register_event_listener(on_event)

    checks = Checks()
    phases = ([("train", phase_train)] if args.chips == 4
              else [("serve", phase_serve), ("kernels", phase_kernels)])
    for name, phase in phases:
        t0 = time.perf_counter()
        phase(checks, seed=args.seed)
        print(f"{name}: phase wall time {time.perf_counter() - t0} s",
              flush=True)
    print(f"compile cache {cache_dir}: {cache_events['hits']} hits, "
          f"{cache_events['misses']} misses")

    if checks.failed:
        print(f"chip_smoke: {len(checks.failed)} checks failed:\n  "
              + "\n  ".join(checks.failed), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
