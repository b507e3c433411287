import os

# Force a virtual 8-device CPU mesh before jax initializes its backends:
# multi-chip sharding paths are validated without TPU hardware (the driver
# dry-runs the real multichip path separately via
# __graft_entry__.dryrun_multichip; `python chip_smoke.py --chips 4` runs
# it on four real chips).  The config update pins the CPU even when the
# environment names another platform first.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

# keep the suite hermetic: no background probe thread per PrometheusServer
# — tests exercise DeviceMonitor directly with an injected probe instead
# (tests/test_tracing.py)
os.environ.setdefault("PATHWAY_DEVICE_PROBE", "0")

import pytest


@pytest.fixture(autouse=True)
def _clear_parse_graph():
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    yield
    G.clear()

