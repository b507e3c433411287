"""Runtime configuration from environment (reference:
python/pathway/internals/config.py:65 PathwayConfig, PATHWAY_* env vars;
src/engine/dataflow/config.rs).

This module is the only reader of ``PATHWAY_*`` variables under
``pathway_tpu/`` (``cli.py`` and ``internals/supervisor.py`` write them
into a child's environment; ``tests/test_doc_sync.py`` holds the rule).
``OPTIONS`` is the whole configuration space: every name once, with its
type, its default, when it is read and its kind.  A tuning value that
nothing sets is a constant beside the code that uses it, not a row here.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any

# name -> (type, default, read, kind)
#   read:  "import"  once, when the owning module is imported
#          "call"    at each call of the function or constructor that
#                    uses it, so setting it after import works
#   kind:  "deployment"  addresses, paths, keys, sizes of a deployment
#          "gate"        switches an observability or control layer
#          "recovery"    limits of the fault-recovery protocol
#          "test-lever"  selects a reference path or injects a fault;
#                        set by tests in processes they cannot patch
OPTIONS: dict[str, tuple[type, Any, str, str]] = {
    # processes, threads, addresses
    "PATHWAY_THREADS": (int, 1, "import", "deployment"),
    "PATHWAY_PROCESSES": (int, 1, "import", "deployment"),
    "PATHWAY_PROCESS_ID": (int, 0, "import", "deployment"),
    "PATHWAY_FIRST_PORT": (int, 10000, "import", "deployment"),
    "PATHWAY_RUN_ID": (str, "", "call", "deployment"),
    "PATHWAY_SPAWN_ARGS": (str, "", "call", "deployment"),
    "PATHWAY_MONITORING_SERVER": (str, None, "import", "deployment"),
    # keys
    "PATHWAY_LICENSE_KEY": (str, None, "import", "deployment"),
    "PATHWAY_LICENSE_PUBKEY": (str, None, "call", "deployment"),
    "PATHWAY_WIRE_UNSAFE_PICKLE": (bool, False, "call", "deployment"),
    # paths
    "PATHWAY_PERSISTENT_STORAGE": (str, "./Cache", "call", "deployment"),
    "PATHWAY_REPLAY_STORAGE": (str, None, "import", "deployment"),
    "PATHWAY_REPLAY_MODE": (str, None, "import", "deployment"),
    "PATHWAY_NATIVE_CACHE": (str, None, "call", "deployment"),
    "PATHWAY_PROFILE_DIR": (str, None, "call", "deployment"),
    "PATHWAY_DIAGNOSTICS_DIR": (str, None, "call", "deployment"),
    "PATHWAY_NODE_TIMING_LOG": (str, None, "call", "deployment"),
    # engine checks
    "PATHWAY_IGNORE_ASSERTS": (bool, False, "import", "deployment"),
    "PATHWAY_RUNTIME_TYPECHECKING": (bool, False, "import", "deployment"),
    "PATHWAY_SLOW_TICK_MS": (float, None, "call", "deployment"),
    # ingest path: sizes of a dispatch and of a packed slab
    "PATHWAY_INGEST_CHUNK": (int, 0, "call", "deployment"),
    "PATHWAY_PACK_TOKEN_BUDGET": (int, None, "call", "deployment"),
    # device: capacity override, health probe
    "PATHWAY_ASSUME_HBM_BYTES": (float, None, "call", "deployment"),
    "PATHWAY_DEVICE_PROBE": (bool, True, "call", "gate"),
    "PATHWAY_DEVICE_PROBE_INTERVAL_S": (float, 300.0, "call", "deployment"),
    # serving tier
    "PATHWAY_SERVE_BATCH_WINDOW_MS": (float, 2.0, "call", "deployment"),
    "PATHWAY_SERVE_MAX_BATCH": (int, 64, "call", "deployment"),
    "PATHWAY_SERVE_QUEUE": (int, 256, "call", "deployment"),
    "PATHWAY_SERVE_CACHE": (int, 1024, "call", "deployment"),
    "PATHWAY_SERVE_TENANT_RATE": (float, 0.0, "call", "deployment"),
    "PATHWAY_SERVE_TENANT_BURST": (float, None, "call", "deployment"),
    "PATHWAY_SLO_P99_MS": (float, None, "call", "deployment"),
    # layer gates
    "PATHWAY_HEALTH": (bool, True, "import", "gate"),
    "PATHWAY_MEMTRACK": (bool, True, "import", "gate"),
    "PATHWAY_QTRACE": (bool, True, "import", "gate"),
    "PATHWAY_SERVING": (bool, True, "import", "gate"),
    "PATHWAY_COSTLEDGER": (bool, True, "import", "gate"),
    "PATHWAY_DEVICE_UTIL": (bool, True, "import", "gate"),
    # "0" off, "1" every epoch, unset: every PATHWAY_TRACE_SAMPLE-th
    "PATHWAY_TRACE": (str, None, "call", "gate"),
    "PATHWAY_TRACE_SAMPLE": (int, 16, "call", "deployment"),
    "PATHWAY_EXCHANGE_TRACE": (bool, False, "import", "gate"),
    "PATHWAY_SANITIZE": (bool, False, "call", "gate"),
    "PATHWAY_PROVENANCE": (bool, False, "call", "gate"),
    "PATHWAY_PROVENANCE_SAMPLE": (int, 1, "call", "deployment"),
    "PATHWAY_PROVENANCE_BUDGET_BYTES": (int, 64 << 20, "call", "deployment"),
    "PATHWAY_PROVENANCE_REQUIRE": (bool, False, "call", "deployment"),
    # fault recovery
    "PATHWAY_FAILOVER": (bool, False, "call", "recovery"),
    "PATHWAY_MAX_FAILOVERS": (int, 3, "call", "recovery"),
    "PATHWAY_REJOIN_TIMEOUT": (float, 30.0, "call", "recovery"),
    # reference paths and fault injection
    "PATHWAY_FAULTS": (str, None, "call", "test-lever"),
    "PATHWAY_DISABLE_FUSION": (bool, False, "call", "test-lever"),
    "PATHWAY_FUSION_FORCE_SKIP": (str, "", "call", "test-lever"),
    "PATHWAY_DISABLE_VECTOR_EXCHANGE": (bool, False, "import", "test-lever"),
    # unset: writer threads where a second core exists
    "PATHWAY_EXCHANGE_WRITERS": (bool, None, "call", "test-lever"),
    "PATHWAY_DISABLE_NATIVE": (bool, False, "call", "test-lever"),
}

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


def _env_bool(raw: str, default):
    v = raw.lower()
    return True if v in _TRUE else False if v in _FALSE else default


def _env_int(raw: str, default):
    try:
        return int(raw)
    except ValueError:
        return default


def _env_float(raw: str, default):
    try:
        return float(raw)
    except ValueError:
        return default


_PARSERS = {bool: _env_bool, int: _env_int, float: _env_float}


def env(name: str):
    """The value of option ``name`` now: its row's default when the
    variable is unset, empty or does not parse as the row's type."""
    type_, default, _, _ = OPTIONS[name]
    raw = os.environ.get(name)
    if not raw:
        return default
    return raw if type_ is str else _PARSERS[type_](raw, default)


@dataclass
class PathwayConfig:
    ignore_asserts: bool = field(
        default_factory=lambda: env("PATHWAY_IGNORE_ASSERTS")
    )
    runtime_typechecking: bool = field(
        default_factory=lambda: env("PATHWAY_RUNTIME_TYPECHECKING")
    )
    threads: int = field(default_factory=lambda: env("PATHWAY_THREADS"))
    processes: int = field(default_factory=lambda: env("PATHWAY_PROCESSES"))
    process_id: int = field(default_factory=lambda: env("PATHWAY_PROCESS_ID"))
    first_port: int = field(default_factory=lambda: env("PATHWAY_FIRST_PORT"))
    license_key: str | None = field(
        default_factory=lambda: env("PATHWAY_LICENSE_KEY")
    )
    monitoring_server: str | None = field(
        default_factory=lambda: env("PATHWAY_MONITORING_SERVER")
    )
    persistence_mode: str | None = None
    replay_storage: str | None = field(
        default_factory=lambda: env("PATHWAY_REPLAY_STORAGE")
    )
    replay_mode: str | None = field(
        default_factory=lambda: env("PATHWAY_REPLAY_MODE")
    )

    @property
    def worker_count(self) -> int:
        return self.threads * self.processes


pathway_config = PathwayConfig()


def set_license_key(key: str | None) -> None:
    pathway_config.license_key = key


def set_monitoring_config(*, server_endpoint: str | None = None, **kwargs) -> None:
    pathway_config.monitoring_server = server_endpoint
    from pathway_tpu.internals import telemetry

    telemetry.set_monitoring_config(server_endpoint=server_endpoint, **kwargs)
