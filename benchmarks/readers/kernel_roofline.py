"""A kernel's share of its roofline, in percent: the time the chip's peaks
allow for the least the kernel must do, over the time it took.

Time: the self time of the device operations whose name matches ``kernel``,
summed over the traced sub-window, per execution of the XLA modules matching
``module`` that the sub-window holds.  Work: ``harness/kernel_counts.py``'s
function ``counts`` gives (operations, bytes) of one call from the attributes
of its ``engine.call`` span; the mean is taken over the calls ``calls``
selects that were dispatched inside the traced sub-window (the middle of the
window, ``cell.py:_trace_middle``).  All of the kernel's time and the least
work are counted, so no reading can pass 100.

None — the metric is left out — when the trace has no such operation or
module (a program without the kernel, a CPU rehearsal) or no such call has
the attributes the count reads.
"""

import re

from benchmarks.harness import kernel_counts, reduce_trace
from benchmarks.harness.registry import Registry
from benchmarks.readers import span_terms

TRACE_SECONDS = 5.0   # cell.py:TRACE_SECONDS


def read(ctx, *, kernel: str, module: str, calls: dict, counts: str):
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    if not span_terms.window_covered(ctx):
        return None
    rx, count = re.compile(kernel), getattr(kernel_counts, counts)
    kernel_ns = executions = 0
    for dev in ctx.trace["devices"].values():
        by_name = reduce_trace.self_time_by_name(dev["ops"])
        kernel_ns += sum(ns for name, ns in by_name.items() if rx.search(name))
        executions += len(reduce_trace.module_durations_ms(dev["modules"], module))
    if kernel_ns <= 0 or executions <= 0:
        return None
    seconds = ctx.window.t1 - ctx.window.t0
    span = min(TRACE_SECONDS, seconds / 2)
    lo = ctx.window.t0 + (seconds - span) / 2
    work = []
    for s in span_terms.whole(ctx):
        if (s["name"] == calls["span"] and lo <= s["start_mono"] < lo + span
                and all(s["attrs"].get(k) == v
                        for k, v in calls.get("where", {}).items())):
            one = count(ctx.cell.config, s["attrs"])
            if one is not None:
                work.append(one)
    if not work:
        return None
    import jax

    peaks = Registry().peaks(jax.devices()[0].device_kind)
    ops = sum(w[0] for w in work) / len(work)
    nbytes = sum(w[1] for w in work) / len(work)
    floor_s = max(ops / (peaks[kernel_counts.PEAK_OF[counts]] * 1e12),
                  nbytes / (peaks["hbm_gbs"] * 1e9))
    return 100.0 * floor_s / (kernel_ns / 1e9 / executions)
