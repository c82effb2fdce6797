"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Must run before the first ``import jax`` anywhere in the test session, which
pytest guarantees by importing conftest first.  All sharding tests target this
virtual mesh; the driver separately validates the multi-chip path via
__graft_entry__.dryrun_multichip.
"""

import os

# Force, don't setdefault: the tests are CPU tests (Pallas kernels run
# interpreted) wherever they are started, a machine with a chip included.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402

# slow/chaos markers are registered in pyproject.toml [tool.pytest.ini_options].


def pytest_sessionfinish(session, exitstatus):
    # Lock-discipline gate: when the suite ran with K8SLLM_LOCKCHECK=1
    # (e.g. `K8SLLM_LOCKCHECK=1 make chaos`), a dirty lockcheck registry
    # (cycles in the acquisition-order graph, unguarded writes to
    # guarded_by fields, release-by-non-owner) fails the whole session
    # even if every individual test passed.
    from k8s_llm_monitor_tpu.devtools import lockcheck

    if not lockcheck.enabled():
        return
    report = lockcheck.registry().report()
    tr = session.config.pluginmanager.get_plugin("terminalreporter")
    if tr is not None:
        tr.write_line(
            f"lockcheck: {len(report['locks'])} instrumented lock(s), "
            f"{len(report['order_edges'])} order edge(s), "
            f"{len(report['cycles'])} cycle(s), "
            f"{len(report['unguarded_writes'])} unguarded write(s), "
            f"{len(report['long_holds'])} long hold(s)")
    if not report["ok"]:
        import json

        print(json.dumps(report, indent=2, default=str))
        session.exitstatus = 1


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax

    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs
