"""Arithmetic from request records to end-to-end metrics.

A record is filled by the load generator (due, submit) and by the service's
observer on the step thread (first, last, done, tokens).  Times are
``time.monotonic()`` seconds.  A request that was refused, failed or did not
finish ranks above every finite value in a percentile, so it shows as a miss.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Iterable, Optional, Sequence


@dataclasses.dataclass
class Request:
    """One request: what to send, then what happened to it."""
    rid: str
    prompt: list[int]
    max_tokens: int
    due_s: Optional[float] = None      # offset from the window's start; None = on completion of the previous
    # filled while running -------------------------------------------------
    due_t: Optional[float] = None      # when it should have been sent
    submit_t: Optional[float] = None   # when it was sent
    first_t: Optional[float] = None    # first emitted token
    last_t: Optional[float] = None     # last emitted token
    done_t: Optional[float] = None     # terminal result seen
    n_tokens: int = 0
    token_ids: list[int] = dataclasses.field(default_factory=list)
    finish: str = ""                   # "eos" | "length" | "error" | "refused"
    error: str = ""
    bad_token: bool = False            # an id outside [0, vocab)

    @property
    def ok(self) -> bool:
        """Finished without error, with exactly max_tokens or an EOS."""
        if self.bad_token or self.done_t is None:
            return False
        if self.finish == "length":
            return self.n_tokens == self.max_tokens
        return self.finish == "eos" and 0 < self.n_tokens <= self.max_tokens


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0 < q <= 100) by the nearest-rank rule: the
    smallest value with at least q% of the sample at or below it.  No
    interpolation, so a miss (``inf``) in the tail stays a miss."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def ttft_ms(r: Request) -> float:
    """Due time -> first emitted token; a miss is ``inf``."""
    if not r.ok or r.first_t is None or r.due_t is None:
        return math.inf
    return (r.first_t - r.due_t) * 1e3


def tpot_ms(r: Request) -> Optional[float]:
    """(last emission - first emission) / (tokens - 1).  Per request, because
    the engine emits in groups of up to ``decode_steps_per_iter`` tokens, so
    raw gaps are zeros and one long one.  None for a request with fewer than
    two tokens (it has no gap); ``inf`` for a miss."""
    if not r.ok:
        return math.inf
    if r.n_tokens < 2:
        return None
    return (r.last_t - r.first_t) / (r.n_tokens - 1) * 1e3


def late_ms(r: Request) -> Optional[float]:
    if r.submit_t is None or r.due_t is None:
        return None
    return (r.submit_t - r.due_t) * 1e3


def _defined(values: Iterable[Optional[float]]) -> list[float]:
    return [v for v in values if v is not None]


@dataclasses.dataclass
class WindowLog:
    """What one measured window produced: the sample and the emissions."""
    t0: float                                  # window start (monotonic)
    t1: float                                  # window end
    sample: list[Request]
    emissions: list[tuple[float, int]]         # (time, tokens) of every emission seen

    def tokens_in_window(self) -> int:
        return sum(n for t, n in self.emissions if self.t0 <= t < self.t1)


END_TO_END: dict[str, Callable[[WindowLog], float]] = {
    "ttft_p50_ms": lambda w: percentile([ttft_ms(r) for r in w.sample], 50),
    "ttft_p95_ms": lambda w: percentile([ttft_ms(r) for r in w.sample], 95),
    "tpot_p95_ms": lambda w: percentile(
        _defined(tpot_ms(r) for r in w.sample), 95),
    "tokens_per_s": lambda w: w.tokens_in_window() / (w.t1 - w.t0),
}
