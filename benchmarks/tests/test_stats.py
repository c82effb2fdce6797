"""(b) Percentile, time to first token and time per output token on
hand-made emission logs."""

import math

import pytest

from benchmarks.harness.stats import (END_TO_END, Request, WindowLog,
                                      percentile, tpot_ms, ttft_ms)


def done(rid, due, first, last, n, max_tokens=None, finish="length"):
    return Request(rid=rid, prompt=[1], max_tokens=max_tokens or n, due_t=due,
                   submit_t=due, first_t=first, last_t=last, done_t=last,
                   n_tokens=n, finish=finish)


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4], 50, 2), ([1, 2, 3, 4], 95, 4), ([5], 95, 5),
    (list(range(1, 101)), 95, 95), (list(range(1, 101)), 50, 50),
    ([1.0, math.inf], 50, 1.0), ([1.0, math.inf], 95, math.inf),
])
def test_percentile_is_nearest_rank(values, q, want):
    assert percentile(values, q) == want


def test_ttft_counts_from_due_time_not_from_submit():
    r = done("a", due=10.0, first=10.25, last=11.0, n=4)
    r.submit_t = 10.2  # the sender was late: the user still waited from 10.0
    assert ttft_ms(r) == pytest.approx(250.0)


def test_tpot_is_per_request_and_skips_a_one_token_answer():
    # 9 tokens: the first at 1.0, then two groups of four; last at 1.8
    assert tpot_ms(done("a", 0.0, 1.0, 1.8, 9)) == pytest.approx(100.0)
    assert tpot_ms(done("one", 0.0, 1.0, 1.0, 1)) is None


@pytest.mark.parametrize("broken", [
    dict(finish="error"), dict(done_t=None), dict(n_tokens=3),
    dict(bad_token=True), dict(finish="refused", done_t=None)])
def test_a_failed_request_is_a_miss(broken):
    r = done("a", 0.0, 0.1, 0.5, 5)
    assert r.ok
    for key, value in broken.items():
        setattr(r, key, value)
    assert not r.ok and ttft_ms(r) == math.inf and tpot_ms(r) == math.inf


def test_eos_may_end_a_request_early():
    assert done("a", 0.0, 0.1, 0.2, 3, max_tokens=8, finish="eos").ok


def test_end_to_end_metrics_over_a_window():
    sample = [done(f"r{i}", due=float(i), first=i + 0.1 * (i + 1),
                   last=i + 0.1 * (i + 1) + 0.4, n=5) for i in range(19)]
    failed = done("bad", 19.0, 19.1, 19.2, 5, finish="error")
    one = done("one", 3.0, 3.05, 3.05, 1)
    w = WindowLog(t0=0.0, t1=20.0, sample=sample + [failed, one],
                  emissions=[(-1.0, 8), (0.0, 8), (5.0, 4), (19.999, 8), (20.0, 8)])
    assert END_TO_END["tokens_per_s"](w) == pytest.approx(1.0)   # 20 tokens in [0, 20)
    assert END_TO_END["ttft_p50_ms"](w) == pytest.approx(1000.0)  # rank 11 of 21
    assert END_TO_END["ttft_p95_ms"](w) == pytest.approx(1900.0)  # rank 20; the miss is rank 21
    # 20 requests have a gap (the one-token answer has none); rank 19 is 100 ms, rank 20 the miss
    assert END_TO_END["tpot_p95_ms"](w) == pytest.approx(100.0)
    w.sample.append(done("bad2", 19.5, 19.6, 19.7, 5, finish="error"))
    assert END_TO_END["ttft_p95_ms"](w) == math.inf
