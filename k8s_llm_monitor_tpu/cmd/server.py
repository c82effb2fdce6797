"""Monitor API server entrypoint.

Parity target: ``/root/reference/cmd/server/main.go:23-172`` — config
load, cluster client with graceful dev-mode degradation (:43-51), metrics
manager start (:82-87), route registration + serve, clean shutdown.

Cluster selection:
- ``--cluster fake``   : in-memory demo cluster (runs anywhere, like the
                         reference's nil-client dev mode but with data)
- ``--cluster kube``   : real API server via kubeconfig/in-cluster
                         (stdlib REST client, monitor/kube_rest.py)
- ``--cluster none``   : no cluster at all (pure degraded mode)

Usage:
    python -m k8s_llm_monitor_tpu.cmd.server --config config.yaml
    python -m k8s_llm_monitor_tpu.cmd.server --cluster fake --port 8081
"""

from __future__ import annotations

import argparse
import logging
import signal
import sys
import threading


def _graceful_shutdown(srv, grace_s: float, log: logging.Logger) -> None:
    """SIGTERM handover: stop admitting, drain within the grace window,
    seal the journal, then unblock ``serve_forever`` so the process exits.

    Order matters: readiness flips to 503 first (via the supervisor's
    TERMINATING state / health DRAINING) so the kube endpoint controller
    stops routing new traffic while inflight generations finish — the
    manifest's preStop sleep covers the propagation delay.
    """
    from k8s_llm_monitor_tpu.observability.flight import get_flight_recorder

    # Last-gasp artifact before teardown mutates any in-flight state; a
    # dump failure (read-only fs, disk full) must never block the drain.
    rec = get_flight_recorder()
    rec.note("sigterm", grace_s=grace_s)
    rec.dump("sigterm", extra={"grace_s": grace_s})
    # Announce draining FIRST: the next fleet stats probe sees it and the
    # router stops dispatching here before the engine starts refusing.
    srv.draining = True
    if srv.signals is not None:
        srv.signals.stop()
        log.info("signal scraper stopped")
    watcher = getattr(srv, "diagnosis_watcher", None)
    if watcher is not None:
        watcher.stop()
        log.info("diagnosis watcher stopped")
    if srv.diagnosis is not None:
        srv.diagnosis.stop()
        log.info("diagnosis pipeline stopped")
    sup = srv.engine_supervisor()
    if sup is not None:
        drained = sup.shutdown(grace_s=grace_s)
        log.info("engine supervisor shut down (drained=%s, journal sealed)",
                 drained)
    else:
        svc = srv.engine_service()
        if svc is not None:
            svc.drain(timeout=grace_s)
            svc.stop(timeout=5.0)
            log.info("engine service drained and stopped")
    if srv.fleet_router() is not None:
        srv.analysis.close()  # stop probes, close replica adapters
        log.info("fleet router closed")
    if srv.manager is not None:
        srv.manager.stop()
    srv.request_shutdown()


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description="k8s-llm-monitor TPU server")
    parser.add_argument("--config", default="", help="config YAML path")
    parser.add_argument("--host", default=None)
    parser.add_argument("--port", type=int, default=None)
    parser.add_argument(
        "--cluster",
        choices=("fake", "kube", "none"),
        default="fake",
        help="cluster backend (default: fake demo cluster)",
    )
    parser.add_argument("--kubeconfig", default="", help="kubeconfig path for --cluster kube")
    parser.add_argument(
        "--llm",
        default="",
        help="override llm.provider (tpu | openai | template)",
    )
    parser.add_argument(
        "--role",
        choices=("replica", "router"),
        default="replica",
        help="replica: serve a local engine (default); router: front the "
             "fleet.replicas URLs with policy routing + failover",
    )
    parser.add_argument(
        "--replicas",
        default="",
        help="router role: comma-separated replica base URLs "
             "(overrides fleet.replicas / FLEET_REPLICAS)",
    )
    args = parser.parse_args(argv)

    from k8s_llm_monitor_tpu.monitor.config import load_config
    from k8s_llm_monitor_tpu.monitor.server import build_server

    config = load_config(args.config or None)
    logging.basicConfig(
        level=logging.DEBUG if config.server.debug else logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s %(message)s",
    )
    log = logging.getLogger("cmd.server")
    if args.host is not None:
        config.server.host = args.host
    if args.port is not None:
        config.server.port = args.port
    if args.llm:
        config.llm.provider = args.llm
    if args.replicas:
        config.fleet.replicas = [
            u.strip() for u in args.replicas.split(",") if u.strip()]

    if args.role == "router":
        # Router role: no local engine, no cluster client — just the fleet
        # behind the same /api/v1/query + /api/v1/analyze API.
        from k8s_llm_monitor_tpu.fleet.frontend import build_router_server

        srv = build_router_server(config)
        if srv.autoscaler is not None:
            srv.autoscaler.start()
        shutdown_started = threading.Event()

        def _on_router_signal(signum, frame):  # noqa: ARG001 — signal API
            if shutdown_started.is_set():
                raise SystemExit(128 + signum)
            shutdown_started.set()
            log.info("signal %d: router shutting down", signum)

            def _stop() -> None:
                from k8s_llm_monitor_tpu.observability.flight import (
                    get_flight_recorder)

                get_flight_recorder().dump("sigterm",
                                           extra={"role": "router"})
                if srv.autoscaler is not None:
                    srv.autoscaler.stop()
                if srv.signals is not None:
                    srv.signals.stop()
                srv.analysis.close()
                srv.request_shutdown()

            threading.Thread(target=_stop, name="graceful-shutdown",
                             daemon=True).start()

        signal.signal(signal.SIGTERM, _on_router_signal)
        signal.signal(signal.SIGINT, _on_router_signal)
        try:
            srv.serve_forever()
        finally:
            if not shutdown_started.is_set():
                if srv.autoscaler is not None:
                    srv.autoscaler.stop()
                if srv.signals is not None:
                    srv.signals.stop()
                srv.analysis.close()
        return 0

    if config.llm.provider == "tpu" and config.llm.tpu.compile_cache_dir:
        # Persistent XLA compilation cache BEFORE any jit runs: a warm
        # restart reuses compiled prefill/decode programs (~seconds)
        # instead of recompiling the full ladder (~minutes on 8B).
        from k8s_llm_monitor_tpu.utils.compile_cache import (
            configure_compile_cache,
        )

        cache_dir, warm = configure_compile_cache(
            config.llm.tpu.compile_cache_dir)
        log.info("XLA compilation cache at %s (%s)", cache_dir,
                 "warm" if warm else "cold")

    backend = None
    if args.cluster == "fake":
        from k8s_llm_monitor_tpu.monitor.cluster import FakeCluster, seed_demo_cluster

        backend = seed_demo_cluster(FakeCluster())
        log.info("using in-memory demo cluster")
    elif args.cluster == "kube":
        try:
            from k8s_llm_monitor_tpu.monitor.kube_rest import KubeRestBackend

            backend = KubeRestBackend.from_kubeconfig(
                args.kubeconfig or config.k8s.kubeconfig or None
            )
            backend.server_version()  # fail fast if unreachable
        except Exception as exc:  # noqa: BLE001 — dev-mode degradation
            log.warning("cluster unreachable (%s) - development mode", exc)
            backend = None

    srv = build_server(config, backend=backend)
    if srv.manager is not None:
        srv.manager.start()
        log.info(
            "metrics manager started (interval %ds)", config.metrics.collect_interval
        )

    # Standing watcher→LLM diagnosis loop: the resource watcher feeds the
    # pipeline's EventHandler; the pipeline's worker thread (started with
    # the HTTP server) turns event bursts into constrained root-cause
    # verdicts behind GET /api/v1/diagnoses.
    srv.diagnosis_watcher = None
    if srv.diagnosis is not None and srv.client is not None:
        from k8s_llm_monitor_tpu.monitor.watcher import Watcher

        srv.diagnosis_watcher = Watcher(
            srv.client, srv.diagnosis.handler,
            namespaces=config.k8s.watch_namespaces)
        srv.diagnosis_watcher.start()
        log.info("diagnosis watcher started (burst threshold %d in %.0fs)",
                 config.diagnosis.burst_threshold, config.diagnosis.window_s)

    # SIGTERM (kubelet) / SIGINT: flip readiness to 503, drain inflight
    # generations within the grace window, seal the request journal, exit.
    # The work runs on a helper thread: httpd.shutdown() deadlocks when
    # called from the thread running serve_forever, and signal handlers
    # run exactly there.
    shutdown_started = threading.Event()

    def _on_signal(signum, frame):  # noqa: ARG001 — signal API
        if shutdown_started.is_set():
            log.warning("second signal %d: exiting immediately", signum)
            raise SystemExit(128 + signum)
        shutdown_started.set()
        log.info("signal %d: graceful shutdown (grace %.0fs)",
                 signum, config.lifecycle.drain_grace_s)
        threading.Thread(
            target=_graceful_shutdown,
            args=(srv, config.lifecycle.drain_grace_s, log),
            name="graceful-shutdown",
            daemon=True,
        ).start()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)

    try:
        srv.serve_forever()
    finally:
        if not shutdown_started.is_set():
            if srv.signals is not None:
                srv.signals.stop()
            if srv.diagnosis_watcher is not None:
                srv.diagnosis_watcher.stop()
            if srv.diagnosis is not None:
                srv.diagnosis.stop()
            sup = srv.engine_supervisor()
            if sup is not None:
                sup.shutdown(grace_s=0.0)
        if srv.manager is not None:
            srv.manager.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
