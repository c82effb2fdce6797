"""(e) The readers of the program's loop spans, on hand-made span lists."""

import json
import types

import pytest

from benchmarks.harness.registry import BENCH_DIR, Registry

MS = 1_000_000
T0, T1 = 100.0, 140.0


def span(name, start, dur, sid="", parent="", **attrs):
    return {"name": name, "start_mono": start, "duration_s": dur,
            "span_id": sid or f"{name}@{start}", "parent_id": parent,
            "attrs": attrs}


def call(start, **attrs):
    return span("engine.call", start, 1.0, **attrs)


SPANS = [
    # three steps: 1.0 s with 0.9 waited, 2.0 s with 1.9 waited (in two
    # pieces), 0.5 s that waited for nothing
    span("engine.step", 101.0, 1.0, sid="s1"),
    span("engine.step.decode", 101.0, 0.06, parent="s1"),
    span("engine.step.wait_device", 101.06, 0.9, parent="s1"),
    span("engine.step.apply", 101.96, 0.04, parent="s1"),
    span("engine.step", 102.0, 2.0, sid="s2"),
    span("engine.step.wait_device", 102.0, 1.0, parent="s2"),
    span("engine.step.wait_device", 103.0, 0.9, parent="s2"),
    span("engine.step", 104.0, 0.5, sid="s3"),
    call(101.0, kind="admit", device_empty=1, bucket=256, rows=1, prompts=1,
         real_tokens=200, padded_tokens=256, cached_tokens=0,
         kv_blocks=100, kv_live_blocks=30),
    call(101.5, kind="chunk", device_empty=0, bucket=512, rows=2, prompts=2,
         real_tokens=568, padded_tokens=1024, cached_tokens=256,
         kv_blocks=100, kv_live_blocks=41),
    call(102.0, kind="decode", device_empty=0, steps=8, lanes=3, slots=4,
         emitted=20, kv_blocks=100, kv_live_blocks=38),
    call(103.0, kind="decode", device_empty=0, steps=4, lanes=4, slots=4,
         emitted=12, kv_blocks=100, kv_live_blocks=35),
    call(104.0, kind="spec", device_empty=0, steps=20, lanes=4, slots=4,
         emitted=33),                       # no census: an unsampled attr set
    span("engine.preempt", 105.0, 0.0),
    span("engine.requeue", 106.0, 0.0),
    span("engine.decode", 102.0, 1.0, emitted=8),
    # what the window's end cuts through counts nowhere: a step that began
    # inside and ends after it, its first wait (the second began too late
    # to be handed over), a call still in flight, a late preemption's twin
    span("engine.step", 139.0, 3.0, sid="s4"),
    span("engine.step.wait_device", 139.0, 0.5, parent="s4"),
    call(139.5, kind="decode", device_empty=1, steps=8, lanes=4, slots=4,
         emitted=32, kv_blocks=100, kv_live_blocks=99),
    span("engine.requeue", 139.9, 0.2),
]


def ctx_of(spans, trace=None):
    return types.SimpleNamespace(
        spans=spans, trace=trace,
        window=types.SimpleNamespace(t0=T0, t1=T1))


def read(metric, ctx):
    """Through the registry: the metric's own file and its reader module."""
    fn, args = Registry().metric_reader(metric)
    return fn(ctx, **args)


@pytest.mark.parametrize("metric, expected", [
    ("loop_wait_device_share", (0.9 + 1.0 + 0.9) / 3.5),
    ("step_host_ms_p50", 100.0),          # self times 100, 100, 500 ms
    ("device_empty_dispatch_share", 1 / 5),
    ("decode_slot_use_share", (20 + 12) / (4 * 8 + 4 * 4)),  # spec is no decode
    ("prefill_token_use_share", 768 / 1280),
    ("prefix_hit_token_share", 256 / 1024),
    ("kv_live_blocks_peak_share", 0.41),
    ("preempt_requeue_in_window", 2.0),
])
def test_span_metric_on_a_hand_made_window(metric, expected):
    value = read(metric, ctx_of(SPANS))
    assert isinstance(value, float) and value == pytest.approx(expected)


@pytest.mark.parametrize("metric", [
    "loop_wait_device_share", "step_host_ms_p50", "device_empty_dispatch_share",
    "decode_slot_use_share", "prefill_token_use_share",
    "kv_live_blocks_peak_share", "preempt_requeue_in_window"])
def test_a_program_without_the_spans_leaves_the_metric_out(metric):
    """The parent commit records none of them: None, never an exception."""
    old_program = [span("engine.decode", 101.0, 1.0, steps=8, emitted=8),
                   span("engine.request", 101.0, 9.0)]
    expected = 0.0 if metric == "preempt_requeue_in_window" else None
    assert read(metric, ctx_of(old_program)) == expected
    assert read(metric, ctx_of([])) is None


def test_a_ring_that_lost_the_windows_start_leaves_the_metric_out(monkeypatch):
    from k8s_llm_monitor_tpu.observability import tracing

    tracer = tracing.Tracer(ring_size=16, sample=1.0)
    root = tracer.new_trace()
    for i in range(40):
        tracer.record("x", float(i), i + 0.5, root)
    assert tracer.overwritten == 24
    monkeypatch.setattr(tracing, "_TRACER", tracer)
    late = [s for s in SPANS if s["start_mono"] >= 102.0]
    for metric in ("loop_wait_device_share", "step_host_ms_p50",
                   "kv_live_blocks_peak_share", "preempt_requeue_in_window"):
        assert read(metric, ctx_of(late)) is None        # oldest span at +2 s
        assert read(metric, ctx_of(SPANS)) is not None   # oldest within 1 s
    monkeypatch.setattr(tracing, "_TRACER", tracing.Tracer(sample=1.0))
    assert read("loop_wait_device_share", ctx_of(late)) is not None


def test_every_new_metric_file_names_a_reader_that_is_there():
    for path in sorted((BENCH_DIR / "metrics").glob("*.json")):
        spec = json.loads(path.read_text())
        assert (BENCH_DIR / "readers" / f"{spec['reader']}.py").is_file(), path.name
