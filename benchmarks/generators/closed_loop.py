"""Closed loop: ``clients`` callers that each wait for a reply.

Parameters (the traffic file): ``clients``, ``prompt_tokens`` and
``max_tokens`` (lognormal, clipped), ``lead_in_s``, ``stratum`` and
``max_rps`` (how many requests to build: clients + max_rps * horizon).  One
sender thread, fed by completion events from the service's observer, sends
a client's next request the moment the previous one completes — not one
blocked thread per client, which would fight the step thread for the
interpreter lock.  Every client starts at the lead-in; the sample is the
requests that completed or failed inside the window, and what is still in
flight when the window ends is cancelled and counted nowhere.
"""

from __future__ import annotations

import math
import queue
import time

from benchmarks.harness import draw
from benchmarks.harness.stats import Request


def plan(params: dict, *, seed: int, vocab: int, seconds: float) -> list[Request]:
    horizon = float(params["lead_in_s"]) + seconds
    stratum = int(params["stratum"])
    n = int(params["clients"]) + math.ceil(float(params["max_rps"]) * horizon)
    n = math.ceil(n / stratum) * stratum  # whole strata: every seed builds the same set
    sizes = draw.rng_for(seed, 0)
    prompt_len = draw.lognormal_int(n, sizes, params["prompt_tokens"], stratum)
    answer_len = draw.lognormal_int(n, sizes, params["max_tokens"], stratum)
    prompts = draw.token_ids(prompt_len, draw.rng_for(seed, 2), vocab)
    return [Request(rid=f"r{i}", prompt=prompts[i], max_tokens=int(answer_len[i]))
            for i in range(n)]


def first_due_s(params: dict) -> float:
    return -float(params["lead_in_s"])


def drive(requests: list[Request], port, *, t0: float, seconds: float) -> None:
    """Sender thread: one request per client, then one per completion."""
    t1 = t0 + seconds
    todo = iter(requests)

    def send_next() -> None:
        req = next(todo, None)
        if req is None:
            raise RuntimeError(
                "closed_loop ran out of built requests: raise max_rps")
        req.due_t = time.monotonic()
        port.send(req)  # a refused request ends at once and frees its client

    for _ in range(int(port.params["clients"])):
        send_next()
    while not port.stop.is_set() and time.monotonic() < t1:
        try:
            port.completions.get(timeout=0.05)
        except queue.Empty:
            continue
        if time.monotonic() < t1:
            send_next()
    port.cancel_open()


def sample(requests: list[Request], *, t0: float, t1: float) -> list[Request]:
    """Requests whose terminal result (or refusal) fell inside the window."""
    return [r for r in requests
            if (r.done_t is not None and t0 <= r.done_t < t1)
            or (r.finish == "refused" and t0 <= r.submit_t < t1)]


def finished(requests: list[Request], *, t0: float, t1: float) -> bool:
    return True  # nothing is drained: the window's end cancels what is open
