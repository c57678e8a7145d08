"""Whole-prompt prefill (``models.prefill``) and the served path's choice of
prefill (``ExecutionEngine.run_task``): dense models fill the cache in one
call, with the logits, cache and generated tokens of the one-token
teacher-forced path; every other family keeps that path unchanged."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.telemetry import FlightRecorder
from repro.models import (PREFILL_FAMILIES, decode_step, forward, init_cache,
                          init_params, prefill)
from repro.serving import ExecutionEngine, HostedModel
from repro.serving.engine import ServedSpans

DECODE = 4
# float32 at the tiny widths: the two paths sum in different orders
TOL = dict(rtol=1e-5, atol=1e-5)
DENSE = sorted(n for n, c in ARCHS.items() if c.arch_type == "dense")
assert PREFILL_FAMILIES == {"dense"}


@functools.lru_cache(maxsize=None)
def _hosted(arch, mid=0):
    cfg = ARCHS[arch].reduced(dtype="float32")
    return HostedModel(mid, cfg, init_params(cfg, jax.random.key(mid)))


@pytest.fixture(scope="module")
def dense():
    return _hosted("mistral-nemo-12b")


@pytest.fixture(scope="module")
def ssm():
    return _hosted("mamba2-780m", 1)


def _prompt(s, seed=0, vocab=100):
    return np.random.default_rng(seed).integers(0, vocab, size=(1, s),
                                                dtype=np.int32)


def _one_token(hosted, prompt, capacity):
    """The one-token path: the prompt teacher-forced through
    ``decode_step``, one position per call."""
    step = jax.jit(lambda p, c, t: decode_step(p, c, t, hosted.cfg))
    cache = init_cache(hosted.cfg, prompt.shape[0], capacity=capacity)
    for i in range(prompt.shape[1]):
        logits, cache = step(hosted.params, cache, jnp.asarray(prompt[:, i]))
    return logits, cache, step


def _greedy(step, params, logits, cache, n):
    out = []
    for _ in range(n):
        nxt = jnp.argmax(logits, axis=-1)
        out.append(np.asarray(nxt))
        logits, cache = step(params, cache, nxt)
    return np.stack(out, axis=1)


@pytest.mark.parametrize("arch", DENSE)
@pytest.mark.parametrize("s", [1, 5, 8])
def test_prefill_logits_are_forwards_last_position(arch, s):
    hosted = _hosted(arch)
    cfg, params = hosted.cfg, hosted.params
    prompt = jnp.asarray(_prompt(s, s))
    logits, _ = prefill(params, init_cache(cfg, 1, capacity=s + 2), prompt,
                        cfg)
    want = forward(params, {"tokens": prompt}, cfg)[0][:, -1]
    assert logits.shape == (1, cfg.vocab)
    np.testing.assert_allclose(logits, want, **TOL)


@pytest.mark.parametrize("arch", DENSE)
@pytest.mark.parametrize("s", [1, 8])
def test_prefill_cache_equals_one_token_cache(arch, s):
    hosted = _hosted(arch)
    cfg, params = hosted.cfg, hosted.params
    prompt = _prompt(s, 10 + s)
    cap = s + DECODE + 1
    _, got = prefill(params, init_cache(cfg, 1, capacity=cap),
                     jnp.asarray(prompt), cfg)
    _, want, _ = _one_token(hosted, prompt, cap)
    assert set(got) == set(want) == {"pos", "k", "v"}
    np.testing.assert_array_equal(got["pos"], want["pos"])
    np.testing.assert_array_equal(got["pos"], [s])
    for name in ("k", "v"):
        assert got[name].dtype == want[name].dtype
        np.testing.assert_allclose(got[name], want[name], **TOL)
        # nothing written past the prompt
        assert not np.any(np.asarray(got[name])[:, :, s:])


@pytest.mark.parametrize("s", [1, 8])
def test_engine_prefill_generates_the_one_token_tokens(dense, s):
    prompt = _prompt(s, 20 + s)
    eng = ExecutionEngine({0: dense}, DECODE)
    served, _ = eng.run_task(0, prompt)
    logits, cache, step = _one_token(dense, prompt, s + DECODE + 1)
    want = _greedy(step, dense.params, logits, cache, DECODE)
    np.testing.assert_array_equal(served, want)
    # the served path's own prefill call, against the one-token logits
    got, _ = eng.prefill_fn(0)(
        dense.params, init_cache(dense.cfg, 1, capacity=s + DECODE + 1),
        jnp.asarray(prompt))
    np.testing.assert_allclose(got, logits, **TOL)


def _recorded_engine(hosted):
    """An engine whose spans land in a flight recorder's ``wall_spans``."""
    spans = ServedSpans(FlightRecorder(1))
    return ExecutionEngine({hosted.model_id: hosted}, DECODE, spans)


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_prefill_calls_per_family(family, request):
    """One program call per task for a dense model, one per prompt position
    (``calls == positions``) for an SSM."""
    hosted = request.getfixturevalue(family)
    eng = _recorded_engine(hosted)
    for s in (8, 1):
        eng.run_task(hosted.model_id, _prompt(s, s))
    prefills = [w.stats for w in eng.spans.recorder.wall_spans
                if w.name == "prefill"]
    calls = 1 if family == "dense" else 8
    assert [(p["calls"], p["positions"]) for p in prefills] == [
        (calls, 8), (1, 1)]


def test_ssm_keeps_the_one_token_path_bit_for_bit(ssm):
    prompt = _prompt(6, 3)
    eng = ExecutionEngine({1: ssm}, DECODE)
    served, _ = eng.run_task(1, prompt)
    step = eng.decode_fn(1)
    cache = init_cache(ssm.cfg, 1, capacity=6 + DECODE + 1)
    toks = jnp.asarray(prompt)
    for i in range(6):
        logits, cache = step(ssm.params, cache, toks[:, i])
    np.testing.assert_array_equal(
        served, _greedy(step, ssm.params, logits, cache, DECODE))


@pytest.mark.parametrize("arch", ["mamba2-780m", "deepseek-v2-236b",
                                  "qwen2-vl-72b"])
def test_prefill_refuses_other_families(arch):
    cfg = ARCHS[arch].reduced(dtype="float32")
    with pytest.raises(ValueError, match="prefill serves"):
        prefill({}, {}, jnp.zeros((1, 4), jnp.int32), cfg)


def test_prefill_refuses_a_prompt_longer_than_the_cache(dense):
    cache = init_cache(dense.cfg, 1, capacity=4)
    with pytest.raises(ValueError, match="exceeds cache capacity"):
        prefill(dense.params, cache, jnp.zeros((1, 5), jnp.int32), dense.cfg)
