"""Run one cell once: set up, warm up, measure a window, print one line."""

from __future__ import annotations

import dataclasses
import json
import math
import shutil
import sys
import tempfile
import threading
import time
from typing import Optional

from benchmarks.harness import flops, reduce_trace, system
from benchmarks.harness.registry import Cell, Registry
from benchmarks.harness.stats import END_TO_END, Request, WindowLog

TRACE_SECONDS = 5.0      # the traced sub-window, in the middle of the run
SAMPLE_EVERY_S = 0.05    # lanes / blocks / in-flight, traced run only
DRAIN_LIMIT_S = 150.0   # the longest answer at a third of a second a token
STEP_TIMEOUT_S = 1100.0  # one warm-up batch may compile a whole-model program


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass(frozen=True)
class Rehearsal:
    """Test-only: a tiny preset on the CPU, the real traffic file scaled."""
    preset: str
    engine: dict
    traffic: dict


@dataclasses.dataclass
class ReaderContext:
    """What a per-layer reader may read."""
    cell: Cell
    window: WindowLog
    requests: list[Request]
    samples: list[dict]          # {"t", "active_slots", "kv_used_share", "in_flight"}
    spans: list[dict]            # the program's Tracer, spans that began in the window
    trace: Optional[dict]        # reduce_trace.load(...) or None
    counters: dict[str, float]


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for key, value in over.items():
        out[key] = (_merge(out[key], value)
                    if isinstance(value, dict) and isinstance(out.get(key), dict)
                    else value)
    return out


class Worker(threading.Thread):
    """A thread whose exception the main thread sees at ``finish``."""

    def __init__(self, target, *args, **kwargs) -> None:
        super().__init__(name=f"bench-{target.__name__}", daemon=True)
        self._call = (target, args, kwargs)
        self.error: Optional[BaseException] = None

    def run(self) -> None:
        target, args, kwargs = self._call
        try:
            target(*args, **kwargs)
        except BaseException as exc:  # noqa: BLE001 — re-raised by finish()
            self.error = exc

    def finish(self, timeout: float) -> None:
        self.join(timeout)
        if self.is_alive():
            raise RuntimeError(f"{self.name} did not stop within {timeout}s")
        if self.error is not None:
            raise self.error


class CompileCounter:
    """Backend compilations JAX reports while it is listening."""

    def __init__(self) -> None:
        import jax.monitoring

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if "backend_compile" in event:
            self.count += 1
            self.seconds += duration


def _devices(cell: Cell, rehearsal: Optional[Rehearsal]):
    import jax

    devices = jax.devices()
    if rehearsal is None and (devices[0].platform != "tpu"
                              or len(devices) < cell.chips):
        raise NoAccelerator(
            f"{cell.name} needs {cell.chips} TPU chip(s); JAX reports "
            f"{len(devices)} x {devices[0].platform}")
    return devices


def _sampler(port, engine, samples: list[dict], t0: float, t1: float) -> None:
    num_blocks = engine.ecfg.num_blocks
    while not port.stop.is_set():
        now = time.monotonic()
        if now >= t1:
            return
        if now >= t0:
            samples.append({
                "t": now,
                "active_slots": engine.active_slots,
                "kv_used_share": 1.0 - engine.allocator.free_blocks / num_blocks,
                "in_flight": port.in_flight()})
        time.sleep(SAMPLE_EVERY_S)


def _trace_middle(t0: float, seconds: float, trace_dir: str, marks: dict) -> None:
    """Profile TRACE_SECONDS in the middle of the window (main thread)."""
    import jax

    span = min(TRACE_SECONDS, seconds / 2)
    time.sleep(max(0.0, t0 + (seconds - span) / 2 - time.monotonic()))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(reduce_trace.CLOCK_MARK):
            marks["mono_ns"] = time.monotonic_ns()
        time.sleep(span)
    finally:
        jax.profiler.stop_trace()


def _breakdown(trace: dict, marks: dict, samples: list[dict]) -> tuple[dict, dict]:
    """(the contract's ``breakdown``, busy/window seconds averaged over chips)."""
    devices = trace["devices"]
    busy = [reduce_trace.busy_ns(d["ops"]) / 1e9 for d in devices.values()]
    window = [reduce_trace.span_ns(d["ops"]) / 1e9 for d in devices.values()]
    first = devices[min(devices)]
    offset = (None if trace["clock_mark_ns"] is None or "mono_ns" not in marks
              else marks["mono_ns"] - trace["clock_mark_ns"])
    gaps = []
    for start, dur, label in reduce_trace.idle_gaps(first["ops"], first["modules"]):
        if offset is not None and samples:
            at = (start + offset) / 1e9
            near = min(samples, key=lambda s: abs(s["t"] - at))
            label += f"|in_flight:{near['in_flight']}|lanes:{near['active_slots']}"
        gaps.append([label, dur / 1e9])
    breakdown = {"device_ops": reduce_trace.top_by_time(first["ops"], 10),
                 "idle_gaps": gaps}
    return breakdown, {"busy_s": sum(busy) / len(busy),
                       "window_s": sum(window) / len(window)}


def _utilisation_line(cell: Cell, wlog: WindowLog, peaks: dict) -> dict:
    """Information, not a metric: operations the window's completed work
    needed over the chip's peak, from the benchmark's own counting rules."""
    cfg = cell.config
    done = [r for r in wlog.sample if r.ok]
    ops = sum(flops.prefill_flops(cfg, len(r.prompt))
              + sum(flops.decode_flops(cfg, len(r.prompt) + i)
                    for i in range(1, r.n_tokens)) for r in done)
    seconds = wlog.t1 - wlog.t0
    return {"info": "end_to_end_utilisation", "requests": len(done),
            "model_flops": ops, "window_s": seconds,
            "share_of_int8_peak": ops / seconds / (peaks["int8_tops"] * 1e12 * cell.chips),
            "share_of_bf16_peak": ops / seconds / (peaks["bf16_tflops"] * 1e12 * cell.chips)}


@dataclasses.dataclass
class Session:
    """A system that is built, probed and warm."""
    cell: Cell
    registry: Registry
    rehearsal: Optional[Rehearsal]
    devices: list
    engine: object
    svc: object
    compiles: CompileCounter
    probe: dict
    t_process: float

    def close(self) -> None:
        """Stop the step thread, then wait for what it left on the device:
        a process that exits under a running program can crash on its way out."""
        import jax

        self.svc.stop(timeout=30.0)
        jax.block_until_ready(jax.device_put(0.0, self.devices[0]) + 1.0)


@dataclasses.dataclass
class Measured:
    window: WindowLog
    requests: list[Request]
    samples: list[dict]
    marks: dict
    counters: dict[str, float]
    drained_s: float
    trace_dir: Optional[str]


def traffic_of(cell: Cell, rehearsal: Optional[Rehearsal]) -> dict:
    return cell.traffic if rehearsal is None else _merge(cell.traffic, rehearsal.traffic)


def set_up(workload: str, seed: int, trace: bool, *,
           registry: Optional[Registry] = None,
           rehearsal: Optional[Rehearsal] = None,
           t_process: Optional[float] = None) -> Session:
    """Build the system from the seed, prove it answers, run every program
    the cell's traffic can reach."""
    t_process = time.monotonic() if t_process is None else t_process
    registry = registry or Registry()
    cell = registry.cell(workload)
    traffic = traffic_of(cell, rehearsal)

    import jax

    from k8s_llm_monitor_tpu.devtools.traceguard import program_cache_size
    from k8s_llm_monitor_tpu.observability.tracing import Tracer, set_tracer
    from k8s_llm_monitor_tpu.utils.compile_cache import configure_compile_cache

    if rehearsal is None:
        cache_dir, warm = configure_compile_cache()
        # The program skips entries that compiled in under a second; a
        # set-up has some sixty of those, and every run would pay them again.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        log(f"compile cache {cache_dir} ({'warm' if warm else 'empty'})")
    devices = _devices(cell, rehearsal)
    compiles = CompileCounter()
    # Spans cost host time on the step thread: off for the end-to-end run,
    # every request in the traced one.
    set_tracer(Tracer(ring_size=1 << 16, sample=1.0) if trace
               else Tracer(ring_size=16, sample=0.0))
    engine, svc = system.build(
        cell.config, seed,
        preset_override=rehearsal.preset if rehearsal else None,
        engine_overrides=rehearsal.engine if rehearsal else None, log=log)
    try:
        log(f"engine up after {time.monotonic() - t_process:.1f}s: "
            f"{engine.ecfg.max_slots} lanes, {engine.ecfg.num_blocks} blocks")
        vocab = engine.cfg.vocab_size
        port = system.LoadPort(svc, traffic, vocab, cell.config["assumed"]["sampling"])
        t = time.monotonic()
        probe = system.probe(port, vocab, seed, STEP_TIMEOUT_S)
        log(f"probe in {time.monotonic() - t:.1f}s ({compiles.count} backend "
            f"compiles, {compiles.seconds:.1f}s so far)")
        batches = system.warm_up(port, engine, traffic, vocab, seed,
                                 STEP_TIMEOUT_S, log)
        log(f"warm after {time.monotonic() - t_process:.1f}s: {batches} batches, "
            f"{program_cache_size(engine)} programs, {compiles.count} backend "
            f"compiles ({compiles.seconds:.1f}s); probe "
            f"{'ok' if probe['ok'] else 'FAILED'}")
    except BaseException:
        svc.stop(timeout=30.0)
        raise
    return Session(cell, registry, rehearsal, devices, engine, svc, compiles,
                   probe, t_process)


def measure(session: Session, traffic: dict, seed: int, seconds: float, *,
            trace: bool = False, sample_series: bool = False) -> Measured:
    """Lead-in, window, drain.  Nothing may compile from the lead-in on."""
    from k8s_llm_monitor_tpu.devtools.traceguard import program_cache_size

    engine, svc, compiles = session.engine, session.svc, session.compiles
    vocab = engine.cfg.vocab_size
    generator = session.registry.generator(traffic["kind"])
    requests = generator.plan(traffic, seed=seed, vocab=vocab, seconds=seconds)
    port = system.LoadPort(svc, traffic, vocab,
                           session.cell.config["assumed"]["sampling"])
    programs_warm, compiles_warm = program_cache_size(engine), compiles.count
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    samples: list[dict] = []
    marks: dict = {}
    t0 = time.monotonic() + 0.05 - generator.first_due_s(traffic)
    t1 = t0 + seconds
    workers = [Worker(generator.drive, requests, port, t0=t0, seconds=seconds)]
    if trace or sample_series:
        workers.append(Worker(_sampler, port, engine, samples, t0, t1))
    for w in workers:
        w.start()
    try:
        if trace:
            _trace_middle(t0, seconds, trace_dir, marks)
        time.sleep(max(0.0, t1 - time.monotonic()))
        programs_end, compiles_end = program_cache_size(engine), compiles.count
        drain_until = time.monotonic() + DRAIN_LIMIT_S
        while (workers[0].is_alive()
               or not generator.finished(requests, t0=t0, t1=t1)) \
                and time.monotonic() < drain_until:
            time.sleep(0.01)
        drained_s = time.monotonic() - t1
    finally:
        port.stop.set()
    for w in workers:
        w.finish(timeout=10.0)
    prefix = engine.prefix_cache
    counters = {
        "compiles_in_window": float((programs_end - programs_warm)
                                    + (compiles_end - compiles_warm)),
        "programs": float(programs_end),
        "prefix_hits": float(prefix.hits if prefix else 0),
        "prefix_misses": float(prefix.misses if prefix else 0),
    }
    if counters["compiles_in_window"]:
        log(f"WARNING: {counters['compiles_in_window']:.0f} COMPILATION(S) INSIDE "
            f"THE WINDOW — the warm-up missed a shape; this run is not a measurement")
    window = WindowLog(t0=t0, t1=t1,
                       sample=generator.sample(requests, t0=t0, t1=t1),
                       emissions=port.emissions)
    return Measured(window, requests, samples, marks, counters, drained_s, trace_dir)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             registry: Optional[Registry] = None,
             rehearsal: Optional[Rehearsal] = None,
             t_process: Optional[float] = None, out=sys.stdout) -> dict:
    """Returns the result object after printing it as the last line of ``out``."""
    session = set_up(workload, seed, trace, registry=registry,
                     rehearsal=rehearsal, t_process=t_process)
    cell, devices = session.cell, session.devices
    try:
        setup_compiles = (session.compiles.count, session.compiles.seconds)
        m = measure(session, traffic_of(cell, rehearsal), seed, seconds, trace=trace)
        faults = system.health_faults(session.svc, session.engine)
        peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                         for d in devices[:cell.chips])
    finally:
        session.close()
    wlog = m.window
    failed = [r for r in wlog.sample if not r.ok]
    if any(faults.values()):
        log(f"engine faults: {faults}")
    for r in failed[:3]:
        log(f"failed request {r.rid}: finish={r.finish!r} tokens={r.n_tokens}/"
            f"{r.max_tokens} error={r.error!r}")

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
    metrics: dict[str, dict] = {}
    result = {"correct": bool(session.probe["ok"] and not failed
                              and not any(faults.values())),
              "attempted": len(wlog.sample), "failed": len(failed),
              "metrics": metrics, "device": device}
    if not trace:
        for metric in cell.end_to_end:
            value = (wlog.t0 - session.t_process if metric.name == "setup_s"
                     else END_TO_END[metric.name](wlog))
            metrics[metric.name] = {"value": value, "unit": metric.unit}
    else:
        from k8s_llm_monitor_tpu.observability.tracing import get_tracer

        try:
            reduced = reduce_trace.load(m.trace_dir)
        finally:
            shutil.rmtree(m.trace_dir, ignore_errors=True)
        log(f"trace inventory: {json.dumps(reduced['inventory'])}")
        if not reduced["devices"] and rehearsal is None:
            raise RuntimeError("the trace holds no device plane with operations")
        spans = [s for s in get_tracer().snapshot()
                 if wlog.t0 <= s["start_mono"] < wlog.t1]
        ctx = ReaderContext(cell=cell, window=wlog, requests=m.requests,
                            samples=m.samples, spans=spans, trace=reduced,
                            counters=m.counters)
        for metric in cell.per_layer:
            read, args = session.registry.metric_reader(metric.name)
            value = read(ctx, **args)
            if value is not None:
                metrics[metric.name] = {"value": value, "unit": metric.unit}
        if reduced["devices"]:  # a CPU rehearsal's trace has no device plane
            result["breakdown"], times = _breakdown(reduced, m.marks, m.samples)
            device.update(times)
            first = reduced["devices"][min(reduced["devices"])]
            result["modules_top"] = reduce_trace.top_by_time(first["modules"], 10)

    bad = [k for k, v in metrics.items() if not math.isfinite(v["value"])]
    if bad:
        raise RuntimeError(
            f"{bad} not finite: too many of the {len(wlog.sample)} requests "
            f"failed or did not finish ({len(failed)}) for this to be a measurement")
    info = {"info": "run", "workload": workload, "seed": seed, "seconds": seconds,
            "sample": len(wlog.sample), "drained_s": m.drained_s,
            "compiles_in_window": m.counters["compiles_in_window"],
            "setup_compiles": setup_compiles[0], "setup_compile_s": setup_compiles[1],
            "programs": m.counters["programs"], "faults": faults,
            "prefix_hits": m.counters["prefix_hits"],
            "probe_ids": session.probe["ids"][0]}
    print(json.dumps(info), file=out)
    if rehearsal is None:
        peaks = session.registry.peaks(devices[0].device_kind)
        print(json.dumps(_utilisation_line(cell, wlog, peaks)), file=out)
    print(json.dumps(result), file=out, flush=True)
    return result
