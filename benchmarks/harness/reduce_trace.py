"""From a profiler trace to a few numbers.

Pure functions over lists of ``(name, start_ns, dur_ns)`` events, plus one
loader that takes those lists out of the ``.xplane.pb`` file the JAX
profiler writes (``jax.profiler.ProfileData``).  A device plane's "XLA Ops"
line holds one event per executed operation, its "XLA Modules" line one per
executed program.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
from typing import Iterable, Optional

Event = tuple[str, int, int]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CLOCK_MARK = "bench_clock_mark"


def busy_ns(events: Iterable[Event]) -> int:
    """Length of the union of the events' intervals: overlapping or nested
    operations count once."""
    total = 0
    end = None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def span_ns(events: Iterable[Event]) -> int:
    """First start to last end."""
    events = list(events)
    if not events:
        return 0
    return max(s + d for _, s, d in events) - min(s for _, s, _ in events)


def idle_share(events: Iterable[Event]) -> Optional[float]:
    events = list(events)
    window = span_ns(events)
    return None if window <= 0 else 1.0 - busy_ns(events) / window


_HLO = re.compile(r"^%?(\S+) = (\(?\w+\[[\d,]*\])")


def base_name(name: str) -> str:
    """A module's ``jit__prefill_sample_fn(8812345)`` -> ``jit__prefill_sample_fn``;
    an operation's HLO text ``%fusion.7 = f32[64,8]{...} fusion(...)`` ->
    ``fusion.7 f32[64,8]`` (the result's shape tells the sampler's sort from
    a layer's product)."""
    hlo = _HLO.match(name)
    if hlo:
        return f"{hlo.group(1)} {hlo.group(2).lstrip('(')}"
    return re.sub(r"\(\d+\)$", "", name)


def self_time_by_name(events: Iterable[Event]) -> dict[str, int]:
    """Time per name with the time of nested events taken out of the event
    that contains them (a ``while`` holds its body's operations)."""
    out: dict[str, int] = {}
    stack: list[list] = []  # [name, end, self_ns]

    def close(upto: Optional[int]) -> None:
        while stack and (upto is None or stack[-1][1] <= upto):
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0) + max(0, self_ns)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([base_name(name), start + dur, dur])
    close(None)
    return out


def top_by_time(events: Iterable[Event], n: int = 10) -> list[list]:
    """``[[name, seconds], ...]`` of the n names with most self time."""
    ranked = sorted(self_time_by_name(events).items(),
                    key=lambda kv: kv[1], reverse=True)
    return [[name, ns / 1e9] for name, ns in ranked[:n]]


def module_durations_ms(modules: Iterable[Event], pattern: str) -> list[float]:
    rx = re.compile(pattern)
    return [dur / 1e6 for name, _, dur in modules if rx.search(name)]


def module_median_ms(modules: Iterable[Event], pattern: str) -> Optional[float]:
    durations = module_durations_ms(modules, pattern)
    return statistics.median(durations) if durations else None


def idle_gaps(ops: Iterable[Event], modules: Iterable[Event],
              n: int = 5) -> list[tuple[int, int, str]]:
    """The n longest intervals with no operation running, as
    ``(start_ns, dur_ns, label)``; the label names the program that ran
    before the gap and the one that ran after it."""
    merged: list[list[int]] = []
    for _, start, dur in sorted(ops, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    gaps = [(a[1], b[0] - a[1]) for a, b in zip(merged, merged[1:])]
    gaps.sort(key=lambda g: g[1], reverse=True)
    mods = sorted(modules, key=lambda e: e[1])

    def around(t0: int, t1: int) -> str:
        before = [m for m in mods if m[1] <= t0]
        after = [m for m in mods if m[1] + m[2] >= t1]
        b = base_name(before[-1][0]) if before else "?"
        a = base_name(after[0][0]) if after else "?"
        return f"after:{b}|before:{a}"

    return [(start, dur, around(start, start + dur)) for start, dur in gaps[:n]]


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(trace_dir: str) -> dict:
    """``{"devices": {id: {"ops": [...], "modules": [...]}}, "clock_mark_ns":
    start of the harness's mark on the trace's clock or None, "inventory":
    plane -> line -> event count}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    devices: dict[int, dict[str, list[Event]]] = {}
    inventory: dict[str, dict[str, int]] = {}
    mark = None
    for plane in data.planes:
        lines = inventory.setdefault(plane.name, {})
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            events = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                      for ev in line.events]
            lines[line.name] = lines.get(line.name, 0) + len(events)
            if m and line.name in (OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == OPS_LINE else "modules"
                dev = devices.setdefault(int(m.group(1)),
                                         {"ops": [], "modules": []})
                dev[key].extend(events)
            elif not m and mark is None:
                for name, start, _ in events:
                    if name == CLOCK_MARK:
                        mark = start
                        break
    return {"devices": devices, "clock_mark_ns": mark, "inventory": inventory}
