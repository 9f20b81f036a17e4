"""The serve path's own instrumentation: ``generate``'s host spans and the
model's named scopes (``attn``, ``mlp``, ``head``) in its compiled steps.

Spans and scopes are metadata: they must not change what is computed, so
the optimised HLO with metadata stripped equals, up to the names of its
instructions, the HLO lowered with every ``jax.named_scope`` turned off.
"""
import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.launch.serve import generate, serve_steps
from repro.models.common import materialize

GEN = 3


@pytest.fixture(scope="module")
def served():
    arch = get_arch("internlm2-1.8b", smoke=True)
    params = materialize(arch.param_spec(), jax.random.key(0))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 8), 0,
                                          arch.cfg.vocab)}
    steps = serve_steps(arch, 8 + GEN + 4)
    generate(arch, steps, params, batch, GEN)          # compile
    return arch, params, batch, steps


def _compiled(steps, params, batch):
    prefill, decode = steps
    _, cache = prefill(params, batch)
    token = {"tokens": jnp.zeros((batch["tokens"].shape[0], 1), jnp.int32)}
    return (prefill.lower(params, batch).compile().as_text(),
            decode.lower(params, cache, token).compile().as_text())


def _strip(hlo: str) -> str:
    """HLO text without metadata and without its table of source frames,
    each instruction renamed by the order of its definition (scopes renumber
    a few ``broadcast_in_dim`` inside fused computations)."""
    lines = [ln for ln in hlo.splitlines() if not re.match(
        r"\d+ |FileNames|FunctionNames|FileLocations|StackFrames", ln)]
    text = re.sub(r",? metadata=\{[^}]*\}", "", "\n".join(lines))
    defined = re.findall(r"^\s*(?:ROOT )?%([\w.-]+) = ", text, re.M)
    names = {n: f"i{k}" for k, n in enumerate(defined)}
    return re.sub(r"%([\w.-]+)", lambda m: "%" + names.get(m[1], m[1]),
                  text)


def test_generate_returns_its_spans_in_order(served):
    arch, params, batch, steps = served
    out = generate(arch, steps, params, batch, GEN)
    names = [n for n, _, _ in out["spans"]]
    assert names == (["serve.prefill", "serve.weights", "serve.sample",
                      "serve.decode"]
                     + ["serve.decode_step", "serve.sample"] * GEN
                     + ["serve.to_host"])
    starts = [s for _, s, _ in out["spans"]]
    assert starts == sorted(starts)
    assert all(e >= s for _, s, e in out["spans"])
    by_name = {n: (s, e) for n, s, e in out["spans"]}
    decode = by_name["serve.decode"]
    assert all(decode[0] <= s and e <= decode[1]
               for n, s, e in out["spans"] if n == "serve.decode_step")
    prefill, weights = by_name["serve.prefill"], by_name["serve.weights"]
    assert prefill[0] <= weights[0] and weights[1] <= prefill[1]


def test_a_second_call_compiles_nothing(served):
    """The serving copy's program, like the steps, is compiled once: a
    call at the shapes of an earlier one compiles nothing."""
    arch, params, batch, steps = served
    generate(arch, steps, params, batch, GEN)
    compiles = []

    def listen(event, *_args, **_kw):
        if "backend_compile" in event:
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        generate(arch, steps, params, batch, GEN)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []


def test_a_decode_step_waits_for_the_cache_it_takes(served):
    """``generate`` dispatches a decode step only once the cache it takes
    is ready: each step's new cache is allocated as it is dispatched, so
    no more than two caches are alive however far the host could run
    ahead."""
    arch, params, batch, (prefill, decode) = served
    ready = []

    def checked(p, cache, b):
        ready.append(all(x.is_ready() for x in jax.tree.leaves(cache)))
        return decode(p, cache, b)
    generate(arch, (prefill, checked), params, batch, GEN)
    assert ready == [True] * GEN


def test_phase_seconds_are_their_spans(served):
    arch, params, batch, steps = served
    out = generate(arch, steps, params, batch, GEN)
    by_name = {n: e - s for n, s, e in out["spans"]}
    assert out["prefill_s"] == by_name["serve.prefill"]
    assert out["decode_s"] == by_name["serve.decode"]


def test_spans_reach_the_profilers_trace(served, tmp_path):
    from jax.profiler import ProfileData

    arch, params, batch, steps = served
    with jax.profiler.trace(str(tmp_path)):
        generate(arch, steps, params, batch, GEN)
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    names = [e.name for p in ProfileData.from_file(path).planes
             for line in p.lines for e in line.events]
    assert names.count("serve.weights") == 1
    assert names.count("serve.prefill") == 1
    assert names.count("serve.decode_step") == GEN
    assert names.count("serve.to_host") == 1


def test_compiled_steps_are_named_and_scoped(served):
    _, params, batch, steps = served
    for step, hlo in zip(("prefill", "decode"),
                         _compiled(steps, params, batch)):
        assert hlo.startswith(f"HloModule jit_{step},")
        for scope in ("attn", "mlp", "head"):
            assert re.search(rf'op_name="jit\({step}\)/[^"]*\b{scope}/',
                             hlo), (step, scope)


def test_scopes_change_no_computation(served, monkeypatch):
    arch, params, batch, steps = served
    scoped = _compiled(steps, params, batch)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()          # trace the steps again, without scopes
    plain = _compiled(serve_steps(arch, 8 + GEN + 4), params, batch)
    assert not any(re.search(r'op_name="[^"]*/(attn|mlp|head)/', h)
                   for h in plain)
    assert [_strip(h) for h in scoped] == [_strip(h) for h in plain]
