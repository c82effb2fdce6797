"""(a) Both generator kinds: seeded, clipped, timed from due time."""

import threading
import time

import pytest

import json

from benchmarks.harness.registry import BENCH_DIR, Registry

REG = Registry()
# the open-loop mix is no cell's yet (PERF.md, Open questions row 1)
STEADY = dict(json.loads((BENCH_DIR / "traffic" / "diagnose-steady.json").read_text()),
              rate_rps=10.0)
LOOPS = REG.cell("qwen2-7b.loops-saturated").traffic
KINDS = [("open_poisson", STEADY), ("closed_loop", LOOPS)]


@pytest.mark.parametrize("kind,traffic", KINDS)
def test_same_seed_same_requests_other_seed_same_sizes(kind, traffic):
    gen = REG.generator(kind)
    a = gen.plan(traffic, seed=2**31 + 5, vocab=1000, seconds=12.0)
    b = gen.plan(traffic, seed=2**31 + 5, vocab=1000, seconds=12.0)
    c = gen.plan(traffic, seed=7, vocab=1000, seconds=12.0)
    assert [(r.prompt, r.max_tokens, r.due_s) for r in a] == \
           [(r.prompt, r.max_tokens, r.due_s) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    # every seed offers the same set of sizes, in another order
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.max_tokens for r in a) == sorted(r.max_tokens for r in c)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]


@pytest.mark.parametrize("kind,traffic", KINDS)
def test_lengths_honour_the_clips_and_the_vocabulary(kind, traffic):
    reqs = REG.generator(kind).plan(traffic, seed=3, vocab=500, seconds=30.0)
    p, a = traffic["prompt_tokens"], traffic["max_tokens"]
    lengths = sorted(len(r.prompt) for r in reqs)
    assert p["min"] <= lengths[0] and lengths[-1] <= p["max"]
    assert lengths[0] == p["min"] and lengths[-1] == p["max"]  # both clips bite
    assert all(a["min"] <= r.max_tokens <= a["max"] for r in reqs)
    assert abs(lengths[len(lengths) // 2] - p["median"]) <= 0.1 * p["median"]
    assert all(0 <= t < 500 for r in reqs for t in r.prompt)


def test_open_loop_offers_exactly_rate_times_seconds_in_the_window():
    gen = REG.generator("open_poisson")
    for seed in (1, 2, 2**31 + 9):
        reqs = gen.plan(STEADY, seed=seed, vocab=100, seconds=12.0)
        inside = [r for r in reqs if 0.0 <= r.due_s < 12.0]
        lead = [r for r in reqs if r.due_s < 0.0]
        assert len(inside) == 120 and len(lead) == round(10.0 * STEADY["lead_in_s"])
        assert min(r.due_s for r in lead) >= -STEADY["lead_in_s"]
        assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)


class FakePort:
    """Stands for the system: records sends, completes on demand."""

    def __init__(self, params, delay_s=0.0):
        import queue
        self.params, self.stop = params, threading.Event()
        self.completions = queue.Queue()
        self.sent, self.delay_s, self.cancelled = [], delay_s, False

    def send(self, req):
        time.sleep(self.delay_s)  # a slow submit makes the sender late
        req.submit_t = time.monotonic()
        self.sent.append(req)
        return True

    def cancel_open(self):
        self.cancelled = True


def test_open_loop_times_from_due_time_and_reports_lateness():
    from benchmarks.harness.stats import late_ms

    gen = REG.generator("open_poisson")
    traffic = dict(STEADY, rate_rps=200.0, lead_in_s=0.05)
    reqs = gen.plan(traffic, seed=1, vocab=100, seconds=0.3)
    port = FakePort(traffic, delay_s=0.004)
    t0 = time.monotonic() + 0.06
    gen.drive(reqs, port, t0=t0, seconds=0.3)
    assert port.sent == reqs
    assert all(r.due_t == pytest.approx(t0 + r.due_s) for r in reqs)
    late = [late_ms(r) for r in reqs]
    assert min(late) >= 0.0 and max(late) >= 4.0  # the sender's own delay shows
    assert len(gen.sample(reqs, t0=t0, t1=t0 + 0.3)) == 60


def test_closed_loop_sends_one_request_per_completion():
    gen = REG.generator("closed_loop")
    traffic = dict(LOOPS, clients=4, lead_in_s=0.0, max_rps=400.0)
    reqs = gen.plan(traffic, seed=1, vocab=100, seconds=0.4)
    port = FakePort(traffic)
    t0 = time.monotonic()
    th = threading.Thread(target=gen.drive, args=(reqs, port),
                          kwargs={"t0": t0, "seconds": 0.4})
    th.start()
    time.sleep(0.1)
    assert len(port.sent) == 4          # the clients, and no more until one completes
    for req in list(port.sent[:3]):
        req.done_t = time.monotonic()
        port.completions.put(req)
    time.sleep(0.15)
    assert len(port.sent) == 7
    th.join(timeout=2.0)
    assert not th.is_alive() and port.cancelled
    assert gen.sample(reqs, t0=t0, t1=t0 + 0.4) == port.sent[:3]
