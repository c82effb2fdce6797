"""Open loop: independent users, Poisson arrivals at a fixed rate.

Parameters (the traffic file): ``rate_rps``, ``prompt_tokens`` and
``max_tokens`` (lognormal, clipped), ``lead_in_s``.  Requests are due on a
schedule whatever the system does; each is timed from when it was due, and
how late the sender ran is on the record (``submit_t - due_t``).  The
lead-in's requests (due before the window) bring the system to its steady
state and are not in the sample.  Exactly ``round(rate * seconds)``
requests are due inside the window, for every seed.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.harness import draw
from benchmarks.harness.stats import Request


def _schedule(n: int, rng, rate: float, span_s: float) -> np.ndarray:
    """n due times in [0, span_s): a seeded order of one set of gaps."""
    gaps = draw.exponential_gaps(n, rng, rate)
    gaps *= span_s / gaps.sum()
    return np.cumsum(gaps) - gaps


def plan(params: dict, *, seed: int, vocab: int, seconds: float) -> list[Request]:
    rate, lead = float(params["rate_rps"]), float(params["lead_in_s"])
    n_lead, n_win = round(rate * lead), round(rate * seconds)
    due = np.concatenate([
        _schedule(n_lead, draw.rng_for(seed, 3), rate, lead) - lead,
        _schedule(n_win, draw.rng_for(seed, 1), rate, seconds)])
    n = n_lead + n_win
    sizes = draw.rng_for(seed, 0)
    prompt_len = np.concatenate([
        draw.lognormal_int(n_lead, sizes, params["prompt_tokens"]),
        draw.lognormal_int(n_win, sizes, params["prompt_tokens"])])
    answer_len = np.concatenate([
        draw.lognormal_int(n_lead, sizes, params["max_tokens"]),
        draw.lognormal_int(n_win, sizes, params["max_tokens"])])
    prompts = draw.token_ids(prompt_len, draw.rng_for(seed, 2), vocab)
    return [Request(rid=f"r{i}", prompt=prompts[i], max_tokens=int(answer_len[i]),
                    due_s=float(due[i])) for i in range(n)]


def first_due_s(params: dict) -> float:
    return -float(params["lead_in_s"])


def drive(requests: list[Request], port, *, t0: float, seconds: float) -> None:
    """Sender thread: sleep until each request is due, then submit it."""
    for req in requests:
        req.due_t = t0 + req.due_s
        delay = req.due_t - time.monotonic()
        if delay > 0 and port.stop.wait(delay):
            return
        port.send(req)


def sample(requests: list[Request], *, t0: float, t1: float) -> list[Request]:
    """Requests due inside the window, sent or not."""
    return [r for r in requests if 0.0 <= r.due_s < t1 - t0]


def finished(requests: list[Request], *, t0: float, t1: float) -> bool:
    """Nothing of the sample is still running (the drain's test)."""
    return all(r.done_t is not None or r.finish == "refused"
               for r in sample(requests, t0=t0, t1=t1))
