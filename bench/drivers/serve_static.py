"""Serving through ``repro.launch.serve.generate``, one static batch at a
time, under open-loop arrivals at the cell's fixed rate.

Set-up: the program's architecture with the configuration's fields, the
reference's weights from the seed (one jitted call, f32 as the program
holds them), the jitted prefill and decode of ``serve_steps`` and one
warm-up ``generate`` at the cell's only shape, (batch, prompt_len).

Window: requests arrive on the traffic's schedule.  Whenever the server is
idle and requests wait, it takes up to ``batch`` of them, first come first
served, fills the rest of the batch with copies of the first prompt (the
program has one compiled shape) and calls ``generate``.  A request's
latency runs from its arrival to ``generate`` returning its tokens to the
host: the whole answer arrives at once, so this is also its time to first
token as its client sees it.  ``generate``'s own ``prefill_s`` and
``decode_s`` are program spans, kept for the per-layer readers.

Check: once the window has closed and the program's state is freed, a
sample of the served requests drawn from the seed goes through the plain
float32 reference, teacher-forced on the served tokens; the widest gap by
which a served token's reference logit lies below the reference's best
(``logit_gap``) must stay within the cell's limit.
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

from bench import traffic as traffic_mod
from bench.harness import Outcome
from bench.programs import lm as program
from bench.reference import lm as ref

#: a traced run traces these batches of its window, [from, to): past the
#: first, whose queue starts empty, and short enough to reduce in seconds
TRACED_BATCHES = (2, 4)


def _setup(cell, mesh, profile):
    import jax
    import jax.numpy as jnp

    from repro.dist.sharding import param_shardings
    from repro.launch.serve import generate, serve_steps

    t = cell.spec.traffic
    arch = program.build_arch(cell.spec.config)
    mcfg = program.model_cfg(cell.spec.config, arch)
    params = program.make_params(
        arch, mcfg, cell.seed,
        param_shardings(arch.param_spec(), mesh, profile))
    steps = serve_steps(arch, t["prompt_len"] + t["output_len"] + 8)
    warm = {"tokens": jnp.zeros((t["batch"], t["prompt_len"]), jnp.int32)}
    jax.block_until_ready(generate(arch, steps, params, warm,
                                   t["output_len"])["tokens"])
    return arch, mcfg, params, steps, generate


def serve(cell, arch, params, steps, generate, reqs):
    """The window: returns (served tokens (n, G+1), finish times (n,),
    per-batch program spans)."""
    import jax.numpy as jnp

    t = cell.spec.traffic
    b, g = t["batch"], t["output_len"]
    n = len(reqs.arrivals)
    served = np.zeros((n, g + 1), np.int64)
    finish = np.full(n, np.nan)
    batches = []
    trace_from, trace_to = TRACED_BATCHES
    nxt = 0
    t0 = time.perf_counter()
    while nxt < n:
        now = time.perf_counter() - t0
        if reqs.arrivals[nxt] > now:
            with cell.span("wait_for_requests"):
                time.sleep(reqs.arrivals[nxt] - now)
            continue
        if cell.trace and len(batches) == trace_from:
            cell.trace_start()
        if cell.trace and len(batches) == trace_to:
            cell.trace_stop()
        idx = np.arange(nxt, min(nxt + b, n))
        idx = idx[reqs.arrivals[idx] <= now]
        nxt = idx[-1] + 1
        with cell.span("inputs"):
            rows = np.concatenate(
                [reqs.prompts[idx],
                 np.repeat(reqs.prompts[idx[:1]], b - len(idx), 0)])
            batch = {"tokens": jnp.asarray(rows)}
        with cell.span("generate"):
            out = generate(arch, steps, params, batch, g)
        served[idx] = out["tokens"][: len(idx)]
        finish[idx] = time.perf_counter() - t0
        batches.append({"batch": b, "prompt": t["prompt_len"], "steps": g,
                        "rows": len(idx), "prefill_s": out["prefill_s"],
                        "decode_s": out["decode_s"]})
        del out
    if cell.trace and trace_from < len(batches) <= trace_to:
        cell.trace_stop()
    return served, finish, batches


def check(cell, mcfg, reqs, served, done, *, low=False) -> float:
    """The widest logit gap over the reference's sample of served
    requests (the control's, with ``low``)."""
    want = cell.spec.workload["reference_sample"]
    rng = np.random.default_rng(cell.seed)
    ids = np.flatnonzero(done)
    pick = np.sort(rng.choice(ids, size=min(want, len(ids)), replace=False))
    t0 = time.perf_counter()
    gaps = ref.served_gaps(mcfg, cell.seed, reqs.prompts[pick], served[pick],
                           low=low)
    print(f"reference{' (control)' if low else ''}: {len(pick)} requests, "
          f"{pick.size and served[pick].size} served tokens, "
          f"{time.perf_counter() - t0:.3f} s", file=sys.stderr)
    return float(np.max(gaps))


class _count_compiles(list):
    """Counts XLA compilations while open (there should be none)."""

    def __enter__(self):
        import jax

        def listen(event, *_args, **_kw):
            if "backend_compile" in event:
                self.append(event)
        self._listen = listen
        jax.monitoring.register_event_duration_secs_listener(listen)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)


def _report(batches, latency, compiles):
    """A summary of the window on standard error, for the reader."""
    rows = [b["rows"] for b in batches]
    print(f"serve: {len(batches)} batches, {np.mean(rows):.2f} requests "
          f"each (max {max(rows)}); prefill_s mean "
          f"{np.mean([b['prefill_s'] for b in batches]):.6f}, decode_s mean "
          f"{np.mean([b['decode_s'] for b in batches]):.6f}; latency s "
          f"p50 {np.percentile(latency, 50):.6f} max {latency.max():.6f}; "
          f"compilations in the window: {len(compiles)}", file=sys.stderr)
    calls = [b["prefill_s"] + b["decode_s"] for b in batches]
    print(f"serve: slowest batch {max(calls):.6f} s (#{int(np.argmax(calls))}"
          f"), median {np.median(calls):.6f} s; every batch, prefill+decode"
          f" s: {' '.join(f'{c:.3f}' for c in calls)}", file=sys.stderr)


def run(cell, control: bool = False):
    from repro.dist.sharding import get_profile, use_mesh_context
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(model=1)
    profile = get_profile(program.build_arch(cell.spec.config).profile)
    with use_mesh_context(mesh, profile):
        arch, mcfg, params, steps, generate = _setup(cell, mesh, profile)
        reqs = traffic_mod.requests(cell.spec.traffic, cell.seed,
                                    cell.seconds, arch.cfg.vocab)
        cell.setup_done()
        with _count_compiles() as compiles:
            served, finish, batches = serve(cell, arch, params, steps,
                                            generate, reqs)
        memory = cell.memory_peak()
        del params, steps
    gc.collect()

    done = np.isfinite(finish)
    latency = finish[done] - reqs.arrivals[done]
    _report(batches, latency, compiles)
    limit = cell.spec.workload["limits"]["logit_gap"]
    checks = {"logit_gap": (check(cell, mcfg, reqs, served, done), limit)}
    if control:
        checks["control_logit_gap"] = (
            check(cell, mcfg, reqs, served, done, low=True), limit)
    return Outcome(
        attempted=len(reqs.arrivals), failed=int((~done).sum()),
        metrics={"latency_p95_ms": 1e3 * float(np.percentile(latency, 95))},
        readings={"batches": batches},
        checks=checks, memory_peak_bytes=memory)
