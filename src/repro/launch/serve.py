"""Serving launcher: batched prefill + decode with a KV/state cache.

``python -m repro.launch.serve --arch <id> --batch 4 --prompt-len 16
--gen 8`` runs prefill on a synthetic prompt batch and decodes tokens,
reporting per-phase timings (compilation included).  Smoke scale by
default; ``--no-smoke`` serves the published widths, and ``--mesh
single-pod`` targets the production mesh.  ``chip_smoke.py`` drives the
same :func:`generate` path on the chip.

``--continuous`` runs the model-guided continuous-batching engine
(``repro.serve``) over a synthetic trace instead of a single static
batch: requests arrive, are admitted against their ECM-predicted finish
times, and the summary reports throughput/latency plus the full event
ledger.  Optionally combine with ``--faults <plan>`` to replay one of
the named fault scenarios.
"""
from __future__ import annotations

import argparse
import json
import time


def _continuous(args) -> int:
    """Trace-driven engine mode: pure virtual clock, no jax needed."""
    from repro.serve import (
        EngineConfig,
        FaultInjector,
        ServeEngine,
        TraceConfig,
        fault_plan,
        synthetic_trace,
    )

    engine = ServeEngine(EngineConfig(seed=args.seed))
    trace = synthetic_trace(
        TraceConfig(n_requests=args.requests), seed=args.seed)
    summary = engine.run(trace, FaultInjector(fault_plan(args.faults)))
    print(json.dumps(summary, indent=1, default=str))
    return 0 if summary["lost"] == 0 else 1


def init_params(arch, mesh, profile, seed: int):
    """Materialise ``arch``'s parameters from ``seed`` in one jitted
    program, each placed on ``mesh`` by ``profile``'s sharding rules."""
    import jax

    from repro.dist.sharding import param_shardings
    from repro.models.common import materialize

    spec = arch.param_spec()
    return jax.jit(lambda k: materialize(spec, k),
                   out_shardings=param_shardings(spec, mesh, profile))(
        jax.random.key(seed))


def serve_steps(arch, max_len: int):
    """The serve path's jitted ``(prefill, decode)`` pair; build it once
    and reuse it, so later calls of :func:`generate` compile nothing."""
    import jax

    def prefill(p, b):
        return arch.prefill(p, b, max_len=max_len)

    return jax.jit(prefill), jax.jit(arch.decode)


class _Span:
    """A host span: ``annotation`` (a ``TraceAnnotation``) opened, and
    ``(name, start, end)`` on ``time.perf_counter`` appended to ``spans``
    when it ends."""

    __slots__ = ("spans", "name", "annotation", "start")

    def __init__(self, spans: list, name: str, annotation):
        self.spans, self.name, self.annotation = spans, name, annotation

    def __enter__(self):
        self.annotation.__enter__()
        self.start = time.perf_counter()

    def __exit__(self, *exc):
        self.spans.append((self.name, self.start, time.perf_counter()))
        self.annotation.__exit__(*exc)


def generate(arch, steps, params, batch, gen: int) -> dict:
    """Prefill ``batch`` and greedily decode ``gen`` tokens.

    Returns ``tokens`` (B, gen + 1): the argmax of the prefill, then of
    each decode step (the first ``gen`` were fed back); ``logits``: the
    prefill's last-position logits, then each decode step's, each
    (B, 1, vocab_padded); the wall seconds of both phases, each ended by
    ``block_until_ready``; and ``spans``, the call's host spans as
    ``(name, start, end)`` on ``time.perf_counter``, in order of start:

    - ``serve.prefill``: prefill dispatched and its logits ready
      (``prefill_s`` is its length); it holds
    - ``serve.weights``: ``arch.serving_params(params)`` dispatched, the
      copy of the weights that the call's prefill and decode steps read,
      made anew in every call (the caller keeps ``params``);
    - ``serve.sample``: the argmax of a step's logits dispatched, first
      the prefill's, then one after each ``serve.decode_step``;
    - ``serve.decode``: the decode loop, its last token ready
      (``decode_s`` is its length); it holds
    - ``serve.decode_step``: the cache of the step before ready, then one
      decode step dispatched;
    - ``serve.to_host``: the tokens joined and copied to the host.

    Each span is also a ``jax.profiler.TraceAnnotation``, so that a
    profiler trace shows it on the device's clock.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    prefill, decode = steps
    spans = []

    def span(name):
        return _Span(spans, name, jax.profiler.TraceAnnotation(name))

    def greedy(logits):
        with span("serve.sample"):
            return jnp.argmax(logits[:, -1, : arch.cfg.vocab],
                              -1)[:, None].astype(jnp.int32)

    with span("serve.prefill"):
        with span("serve.weights"):
            weights = arch.serving_params(params)
        logits, cache = prefill(weights, batch)
        jax.block_until_ready(logits)

    all_logits = [logits]
    toks = [greedy(logits)]
    with span("serve.decode"):
        for _ in range(gen):
            with span("serve.decode_step"):
                # one step in flight: a step's new cache is allocated as it
                # is dispatched, so a host that ran ahead would hold one
                # cache for every step it is ahead
                jax.block_until_ready(cache)
                logits, cache = decode(weights, cache, {"tokens": toks[-1]})
            all_logits.append(logits)
            toks.append(greedy(logits))
        jax.block_until_ready(toks[-1])
    with span("serve.to_host"):
        tokens = np.asarray(jnp.concatenate(toks, 1))
    seconds = {name: end - start for name, start, end in spans}
    return {"tokens": tokens, "logits": all_logits,
            "prefill_s": seconds["serve.prefill"],
            "decode_s": seconds["serve.decode"],
            "spans": sorted(spans, key=lambda sp: sp[1])}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--mesh", default="host",
                    choices=("host", "single-pod", "multi-pod"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="run the ECM-guided continuous-batching engine "
                         "over a synthetic trace (repro.serve)")
    ap.add_argument("--requests", type=int, default=64,
                    help="trace length for --continuous")
    ap.add_argument("--faults", default="none",
                    help="fault plan for --continuous "
                         "(none/device_loss/slow_step/kv_corruption)")
    args = ap.parse_args()

    if args.continuous:
        return _continuous(args)

    import jax.numpy as jnp

    from repro.configs import ARCH_NAMES, get_arch
    from repro.configs.base import ShapeSpec
    from repro.dist.sharding import get_profile, use_mesh_context
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_host_mesh, make_production_mesh

    if args.arch not in ARCH_NAMES:
        ap.error(f"--arch must be one of {ARCH_NAMES}")

    use_compile_cache()
    arch = get_arch(args.arch, smoke=args.smoke)
    if not arch.has_decoder:
        print(f"{arch.name}: encoder-only, nothing to serve")
        return 0
    multi_pod = args.mesh == "multi-pod"
    mesh = (make_host_mesh(model=1) if args.mesh == "host"
            else make_production_mesh(multi_pod=multi_pod))
    profile = get_profile(arch.profile, multi_pod=multi_pod)
    max_len = args.prompt_len + args.gen + 8

    shape = ShapeSpec("cli_prefill", seq_len=args.prompt_len,
                      global_batch=args.batch, kind="prefill")
    batch = {k: jnp.asarray(v)
             for k, v in arch.make_batch(shape, seed=args.seed).items()}

    with use_mesh_context(mesh, profile, multi_pod=multi_pod):
        params = init_params(arch, mesh, profile, args.seed)
        out = generate(arch, serve_steps(arch, max_len), params, batch,
                       args.gen)

    # --gen 0 is a prefill-only run: no decode steps happened, so a
    # per-token decode time does not exist (it is null, not 0/0)
    print(json.dumps({
        "arch": arch.name,
        "prefill_s": round(out["prefill_s"], 4),
        "decode_s_per_tok": (round(out["decode_s"] / args.gen, 4)
                             if args.gen > 0 else None),
        "tokens": out["tokens"][:, 1:].tolist(),
    }, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
