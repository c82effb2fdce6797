"""A device call's own seconds, from inside the program
(serving/engine.py: ``_call_ready``, ``_reconcile_one``,
``_count_device_time``).

The device runs calls in the order they were enqueued, so a call holds the
head of its queue from the later of its dispatch and the previous call's
finish until its own finish; both finishes are the step thread's first
sightings.  Scripted calls on stub arrays whose readiness the test sets,
under a clock the test moves, pin the arithmetic; a real engine with
recording stubs around its programs pins the span's edges.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

import jax

from k8s_llm_monitor_tpu.models import llama
from k8s_llm_monitor_tpu.models.config import ModelConfig
from k8s_llm_monitor_tpu.observability.tracing import (
    Tracer,
    get_tracer,
    set_tracer,
)
from k8s_llm_monitor_tpu.serving import engine as engine_mod
from k8s_llm_monitor_tpu.serving.engine import (
    EngineConfig,
    InferenceEngine,
    SamplingParams,
)

CFG = ModelConfig(name="t", vocab_size=300, hidden_size=32,
                  intermediate_size=64, num_layers=2, num_heads=4,
                  num_kv_heads=2, dtype="float32", rope_theta=10_000.0)
# tests/test_tracing.py's shapes, so the jit cache is shared.
ECFG = dict(max_slots=4, num_blocks=64, block_size=8, max_blocks_per_seq=16,
            prefill_buckets=(16,), max_prefills_per_step=4,
            decode_steps_per_iter=4, prefix_cache_entries=0)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture
def traced():
    before = get_tracer()
    tracer = Tracer(ring_size=4096, sample=1.0, seed=35)
    set_tracer(tracer)
    try:
        yield tracer
    finally:
        set_tracer(before)


# -- scripted calls ------------------------------------------------------------


class _Clock:
    """The engine module's ``time``, with a monotonic clock the test sets."""

    def __init__(self) -> None:
        self.now = 100.0

    def monotonic(self) -> float:
        return self.now

    def __getattr__(self, name):
        return getattr(time, name)


class _Result:
    """A call's result: ready when the test says so; fetching it before
    then blocks until the device finishes, at ``finish_at``."""

    def __init__(self, clock: _Clock, finish_at: float) -> None:
        self.clock, self.finish_at, self.ready = clock, finish_at, False

    def is_ready(self) -> bool:
        return self.ready

    def __array__(self, dtype=None, copy=None):
        if not self.ready:
            self.clock.now = max(self.clock.now, self.finish_at)
            self.ready = True
        return np.zeros((4, ECFG["max_slots"]), np.int32)


@pytest.fixture
def scripted(params, traced, monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(engine_mod, "time", clock)
    eng = InferenceEngine(CFG, params, EngineConfig(**ECFG), eos_id=-1)

    def dispatch(at: float, finish_at: float) -> _Result:
        clock.now = at
        result = _Result(clock, finish_at)
        eng._inflight.append(engine_mod._Inflight(
            kind="decode", call_id=eng._next_call_id, arr=result, lanes=[],
            t0=at, span_attrs={"kind": "decode",
                               "call_id": eng._next_call_id}))
        eng._next_call_id += 1
        return result

    def calls():
        spans = [s for s in traced.snapshot() if s["name"] == "engine.call"]
        return {s["attrs"]["call_id"]: s for s in spans}

    return eng, clock, dispatch, calls


def _check(calls, want: dict[int, tuple[float, float, float, int]]) -> None:
    """call id -> (span start, span end, device_s, waited)."""
    got = calls()
    assert set(got) == set(want)
    for cid, (start, end, device_s, waited) in want.items():
        span = got[cid]
        assert span["start_mono"] == pytest.approx(start)
        assert span["start_mono"] + span["duration_s"] == pytest.approx(end)
        assert span["attrs"]["device_s"] == pytest.approx(device_s)
        assert span["attrs"]["waited"] == waited


def test_back_to_back_calls_split_the_queue_at_the_earlier_finish(scripted):
    eng, clock, dispatch, calls = scripted
    dispatch(at=0.0, finish_at=10.0)
    dispatch(at=1.0, finish_at=16.0)    # queued behind the first
    clock.now = 2.0
    eng._reconcile_one()                # blocks until 10
    eng._reconcile_one()                # blocks until 16
    _check(calls, {0: (0.0, 10.0, 10.0, 1), 1: (1.0, 16.0, 6.0, 1)})
    assert eng.device_seconds == {"decode": pytest.approx(16.0)}


def test_an_idle_device_does_not_count(scripted):
    eng, clock, dispatch, calls = scripted
    dispatch(at=0.0, finish_at=5.0)
    eng._reconcile_one()
    dispatch(at=20.0, finish_at=27.0)   # the device idled from 5 to 20
    eng._reconcile_one()
    _check(calls, {0: (0.0, 5.0, 5.0, 1), 1: (20.0, 27.0, 7.0, 1)})
    assert eng.device_seconds == {"decode": pytest.approx(12.0)}


def test_a_dispatch_check_stamps_what_it_finds_finished(scripted):
    eng, clock, dispatch, calls = scripted
    first = dispatch(at=0.0, finish_at=4.0)
    second = dispatch(at=1.0, finish_at=9.0)
    first.ready = True
    clock.now = 6.0
    assert not eng._device_empty()      # the next dispatch's check, at 6
    second.ready = True
    clock.now = 12.0
    eng._reconcile_one()                # stamped at 6: no wait
    eng._reconcile_one()                # ready at the fetch's start: 12
    _check(calls, {0: (0.0, 6.0, 6.0, 0), 1: (1.0, 12.0, 6.0, 0)})


def test_the_drain_stamps_a_call_before_its_fetch(scripted):
    eng, clock, dispatch, calls = scripted
    first = dispatch(at=0.0, finish_at=4.0)
    dispatch(at=1.0, finish_at=9.0)
    first.ready = True
    clock.now = 6.0
    eng.step()   # the drain reconciles the first, then waits on the second
    _check(calls, {0: (0.0, 6.0, 6.0, 0), 1: (1.0, 9.0, 3.0, 1)})
    assert not eng._inflight


# -- the span's edges on a real engine ----------------------------------------


@pytest.fixture(scope="module")
def recorded(params):
    """A prompt longer than the top bucket (chunk calls) beside a short one
    (an admission call), then decode: when each program was entered, and
    each call's dispatch and ready stamps as the engine counted them."""
    before = get_tracer()
    tracer = Tracer(ring_size=4096, sample=1.0, seed=36)
    set_tracer(tracer)
    try:
        eng = InferenceEngine(CFG, params, EngineConfig(**ECFG), eos_id=-1)
        entered: dict[str, list[float]] = {"admit": [], "chunk": [],
                                           "decode": []}

        def recording(kind, fn):
            def wrapped(*args, **kwargs):
                entered[kind].append(time.monotonic())
                return fn(*args, **kwargs)
            return wrapped

        eng._prefill_greedy = recording("admit", eng._prefill_greedy)
        eng._prefill_chunk_greedy = recording(
            "chunk", eng._prefill_chunk_greedy)
        eng._dispatch_decode_call = recording(
            "decode", eng._dispatch_decode_call)
        stamps: dict[int, tuple[float, float]] = {}
        counted = eng._count_device_time

        def count(call, waited):
            counted(call, waited)
            stamps[call.call_id] = (call.t0, call.t_ready)

        eng._count_device_time = count
        eng.generate([list(range(3, 43)), [5, 6, 7, 8, 9]],
                     SamplingParams(max_tokens=6))
        spans = [s for s in tracer.snapshot() if s["name"] == "engine.call"]
    finally:
        set_tracer(before)
    return entered, stamps, spans


@pytest.mark.parametrize("kind", ["admit", "chunk", "decode"])
def test_a_call_spans_dispatch_to_first_seen_ready(recorded, kind):
    entered, stamps, spans = recorded
    mine = sorted((s for s in spans if s["attrs"]["kind"] == kind),
                  key=lambda s: s["attrs"]["call_id"])
    assert mine and len(mine) == len(entered[kind])
    for span, entry in zip(mine, entered[kind]):
        t0, t_ready = stamps[span["attrs"]["call_id"]]
        assert span["start_mono"] == t0 <= entry   # before the program
        assert span["start_mono"] + span["duration_s"] == pytest.approx(
            t_ready, abs=1e-9)
        assert 0 <= span["attrs"]["device_s"] <= span["duration_s"] + 1e-9
