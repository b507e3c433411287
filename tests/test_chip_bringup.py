"""What the chip bring-up added, checked on the CPU: the compile cache's
one place, the in-process device probe, one process per chip in the
launchers, keyword forwarding in the REST servers, delete detection in
the fs connector, and chip_smoke.py failing without a chip."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import pytest

import pathway_tpu as pw

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_python(code_or_args, env_overrides=None, timeout=300, cwd=REPO):
    env = dict(os.environ, PYTHONPATH=REPO)
    for key, value in (env_overrides or {}).items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    args = (
        [sys.executable, "-c", code_or_args]
        if isinstance(code_or_args, str)
        else [sys.executable, *code_or_args]
    )
    return subprocess.run(
        args, env=env, cwd=cwd, capture_output=True, text=True, timeout=timeout
    )


# -- compile cache -----------------------------------------------------------

_CACHE_PROBE = (
    "import sys, pathway_tpu\n"
    "from pathway_tpu.internals import compile_cache\n"
    "assert 'jax' not in sys.modules, 'import pathway_tpu imported jax'\n"
    "path = compile_cache.configure()\n"
    "import jax\n"
    "print(path)\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def test_compile_cache_defaults_to_the_fixed_in_checkout_path():
    proc = _run_python(_CACHE_PROBE, {"JAX_COMPILATION_CACHE_DIR": None})
    assert proc.returncode == 0, proc.stderr[-2000:]
    returned, configured = proc.stdout.split()
    assert returned == configured == os.path.join(REPO, ".jax_cache")
    # fixed: nothing of the process, the clock or tempfile is in it
    again = _run_python(_CACHE_PROBE, {"JAX_COMPILATION_CACHE_DIR": None})
    assert again.stdout == proc.stdout


def test_compile_cache_honours_the_environment_variable(tmp_path):
    placed = str(tmp_path / "cc")
    proc = _run_python(_CACHE_PROBE, {"JAX_COMPILATION_CACHE_DIR": placed})
    assert proc.returncode == 0, proc.stderr[-2000:]
    # jax read the variable itself; configure() set no other directory
    assert proc.stdout.split() == [placed, placed]


def test_no_other_cache_directory_is_set_in_code():
    hits = []
    for root, _dirs, files in os.walk(REPO):
        if any(part.startswith(".") for part in root.split(os.sep)):
            continue
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path, encoding="utf-8") as fh:
                text = fh.read()
            if "jax_compilation_cache_dir" in text or (
                "compilation_cache" in text and "set_cache_dir" in text
            ):
                hits.append(os.path.relpath(path, REPO))
    assert sorted(hits) == [
        "pathway_tpu/internals/compile_cache.py",
        "tests/test_chip_bringup.py",
    ]


# -- device probe ------------------------------------------------------------


def test_default_device_probe_starts_no_process(monkeypatch):
    from pathway_tpu.internals import device_probe

    def no_process(*a, **k):
        raise AssertionError("the device probe started a process")

    monkeypatch.setattr(subprocess, "Popen", no_process)
    monkeypatch.setattr(subprocess, "run", no_process)
    monkeypatch.setattr(os, "fork", no_process)
    rtt, err = device_probe.device_probe(timeout_s=60.0)
    assert err is None and rtt is not None and rtt >= 0.0
    monitor = device_probe.DeviceMonitor(interval_s=1.0)
    assert monitor.probe is device_probe.device_probe
    assert monitor.probe_once()["healthy"] is True
    assert monitor.probe_once()["probes"] == 2


def test_device_probe_without_jax_reports_healthy_and_imports_nothing():
    proc = _run_python(
        "import sys\n"
        "from pathway_tpu.internals.device_probe import device_probe\n"
        "assert device_probe() == (None, None)\n"
        "assert 'jax' not in sys.modules\n"
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_busy_device_is_late_not_dead(monkeypatch):
    """A dispatch that outlives the deadline reads as down; the same
    outstanding probe is waited on again (no pile-up) and, once it
    completes, the next round is healthy."""
    from pathway_tpu.internals import device_probe

    release = threading.Event()
    started = []

    def slow_run(self):
        started.append(self)
        release.wait(30)
        self.result = (1.0, None)
        self.done.set()

    monkeypatch.setattr(device_probe._InProcessProbe, "_run", slow_run)
    monkeypatch.setattr(device_probe, "_outstanding", None)
    rtt, err = device_probe.device_probe(timeout_s=0.05)
    assert rtt is None and "outstanding" in err
    rtt, err = device_probe.device_probe(timeout_s=0.05)
    assert rtt is None and len(started) == 1
    release.set()
    assert device_probe.device_probe(timeout_s=10.0) == (1.0, None)
    assert len(started) == 1
    assert device_probe.device_probe(timeout_s=10.0) == (1.0, None)
    assert len(started) == 2


# -- one process per chip ----------------------------------------------------


def test_worker_chip_env_gives_each_worker_its_own_chip(monkeypatch):
    from pathway_tpu.internals import supervisor

    monkeypatch.setattr(supervisor, "tpu_chip_count", lambda: 4)
    envs = [supervisor.worker_chip_env(i, 4, {}) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    assert supervisor.worker_chip_env(1, 2, {"JAX_PLATFORMS": "tpu,cpu"})
    # the CPU pinned by the caller: nothing changes
    assert supervisor.worker_chip_env(0, 8, {"JAX_PLATFORMS": "cpu"}) == {}
    # chips assigned by the caller: left alone
    assert supervisor.worker_chip_env(0, 2, {"TPU_VISIBLE_CHIPS": "3"}) == {}
    with pytest.raises(ValueError, match="5 worker processes but only 4"):
        supervisor.worker_chip_env(0, 5, {})
    # no TPU on the host: nothing changes, however many workers
    monkeypatch.setattr(supervisor, "tpu_chip_count", lambda: 0)
    assert supervisor.worker_chip_env(0, 64, {}) == {}


def test_spawn_refuses_more_workers_than_chips(monkeypatch, capsys):
    from pathway_tpu import cli
    from pathway_tpu.internals import supervisor

    monkeypatch.setattr(supervisor, "tpu_chip_count", lambda: 4)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    started = []
    monkeypatch.setattr(
        subprocess, "Popen", lambda *a, **k: started.append((a, k))
    )
    assert cli.main(["spawn", "-n", "5", "--", "true"]) == 2
    assert not started
    assert "5 worker processes but only 4 TPU chip" in capsys.readouterr().err


def test_spawn_assigns_chips_and_leaves_the_cpu_alone(monkeypatch):
    from pathway_tpu import cli
    from pathway_tpu.internals import supervisor

    monkeypatch.setattr(supervisor, "tpu_chip_count", lambda: 4)
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    envs = []

    class _Done:
        def wait(self):
            return 0

    def fake_popen(program, env):
        envs.append(env)
        return _Done()

    monkeypatch.setattr(subprocess, "Popen", fake_popen)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert cli.main(["spawn", "-n", "2", "--", "true"]) == 0
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1"]
    assert [e["PATHWAY_PROCESS_ID"] for e in envs] == ["0", "1"]
    envs.clear()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert cli.main(["spawn", "-n", "8", "--", "true"]) == 0
    assert len(envs) == 8 and not any("TPU_VISIBLE_CHIPS" in e for e in envs)


def test_tpu_chip_count_loads_no_jax():
    proc = _run_python(
        "import sys\n"
        "from pathway_tpu.internals.supervisor import tpu_chip_count\n"
        "print(tpu_chip_count())\n"
        "assert 'jax' not in sys.modules\n"
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "0"  # this sandbox has no chip


# -- sharding errors ---------------------------------------------------------


def test_encoder_batch_that_cannot_shard_is_an_error():
    from pathway_tpu.models.minilm import SentenceEncoder
    from pathway_tpu.models.transformer import TransformerConfig

    tiny = TransformerConfig(
        vocab_size=512, hidden=32, layers=1, heads=2, mlp_dim=64, max_len=32
    )
    enc = SentenceEncoder("bringup-tiny", config=tiny, max_len=16)

    class _WideMesh:  # a dp axis wider than the 8-row batch bucket
        axis_names = ("dp",)
        shape = {"dp": 16}

    enc.mesh = _WideMesh()
    with pytest.raises(ValueError, match="does not divide over the 16"):
        enc.encode(["one doc"])


# -- REST servers forward their keywords -------------------------------------


@pytest.mark.parametrize("threaded", [False, True])
def test_rest_server_run_forwards_keywords_to_pw_run(monkeypatch, threaded):
    from pathway_tpu.internals import runner
    from pathway_tpu.xpacks.llm.servers import BaseRestServer

    seen = {}
    done = threading.Event()

    def fake_run(**kwargs):
        seen.update(kwargs)
        done.set()

    monkeypatch.setattr(runner, "run", fake_run)
    server = BaseRestServer("127.0.0.1", 18999)
    thread = server.run(
        threaded=threaded,
        with_cache=False,
        with_http_server=True,
        mesh="dp=4",
        slo=25.0,
        autocommit_duration_ms=5,
    )
    assert done.wait(10)
    if threaded:
        thread.join(10)
        assert not thread.is_alive()
    else:
        assert thread is None
    assert seen == {
        "with_http_server": True,
        "mesh": "dp=4",
        "slo": 25.0,
        "autocommit_duration_ms": 5,
    }


# -- fs connector: a deleted file retracts its rows --------------------------


def test_streaming_fs_read_retracts_rows_of_a_deleted_file(tmp_path):
    from pathway_tpu.internals.runner import last_engine

    for i in range(2):
        with open(tmp_path / f"part{i}.jsonl", "w") as fh:
            for j in range(3):
                fh.write(json.dumps({"data": f"doc{i}_{j}"}) + "\n")
    table = pw.io.jsonlines.read(
        str(tmp_path),
        schema=pw.schema_from_types(data=str),
        mode="streaming",
        refresh_interval=0.1,
    )
    live = set()
    lock = threading.Lock()

    def on_change(key, row, time, is_addition):  # noqa: A002
        with lock:
            (live.add if is_addition else live.discard)(row["data"])

    pw.io.subscribe(table, on_change=on_change)
    runner = threading.Thread(
        target=pw.run, kwargs={"autocommit_duration_ms": 20}, daemon=True
    )
    runner.start()

    def wait_for(expected):
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            with lock:
                if live == expected:
                    return
            time.sleep(0.05)
        raise AssertionError(f"live rows {sorted(live)}")

    everything = {f"doc{i}_{j}" for i in range(2) for j in range(3)}
    try:
        wait_for(everything)
        os.remove(tmp_path / "part0.jsonl")
        wait_for({d for d in everything if d.startswith("doc1_")})
    finally:
        last_engine().terminate_flag.set()
        runner.join(30)
    assert not runner.is_alive()


# -- the chip entry points fail without a chip -------------------------------


def test_chip_smoke_without_a_tpu_exits_nonzero_and_prints_no_result(tmp_path):
    proc = _run_python(
        [os.path.join(REPO, "chip_smoke.py"), "--out", str(tmp_path)],
        {"JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_chip_smoke_dry_run_passes_at_tiny_size(tmp_path):
    proc = _run_python(
        [os.path.join(REPO, "chip_smoke.py"), "--dry-run", "--out",
         str(tmp_path)],
        {
            "JAX_PLATFORMS": "cpu",
            "JAX_COMPILATION_CACHE_DIR": None,
            "XLA_FLAGS": None,  # conftest's eight virtual devices
        },
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    report_line, verdict_line = proc.stdout.strip().splitlines()
    # the last line is the verdict alone: exactly these keys, nothing else
    assert json.loads(verdict_line) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    result = json.loads(report_line)
    assert result["ok"] is True
    assert result["stamp"]["dry_run"] is True
    assert result["stamp"]["compile_cache_dir"] == os.path.join(
        REPO, ".jax_cache"
    )
    assert list(result["phases"]) == [
        "stamp", "sync", "kernels", "serve", "knn_route"
    ]
    assert all(p["pass"] for p in result["phases"].values())
    assert result["phases"]["kernels"]["interpret"] is True
    serve = result["phases"]["serve"]
    assert serve["queries_answered"] == 12 and serve["deleted_docs_gone"]
    assert serve["device_monitor"]["probes"] >= 2


# -- the flash kernel under a mesh -------------------------------------------


@pytest.mark.parametrize(
    "shape,names", [((4,), ("dp",)), ((4, 2), ("dp", "tp")), ((8,), ("knn",))]
)
def test_flash_attention_runs_per_device_under_a_mesh(shape, names):
    """Mosaic refuses to partition a kernel automatically (found on four
    chips, PR 21), so under a mesh forward() runs it inside shard_map; the
    result must be the dense path's whatever the axes are called."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from pathway_tpu.models.transformer import (
        TransformerConfig,
        forward,
        init_params,
        param_sharding_rules,
    )

    n = int(np.prod(shape))
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices (conftest emulates 8)")
    config = TransformerConfig(
        vocab_size=512, hidden=64, layers=1, heads=4, mlp_dim=128, max_len=288
    )
    params = init_params(jax.random.PRNGKey(0), config)
    rng = np.random.default_rng(0)
    ids = rng.integers(4, 512, size=(8, 272)).astype(np.int32)
    mask = np.ones_like(ids)
    mask[3, 200:] = 0
    dense = forward(params, config, ids, mask, use_flash=False)

    mesh = Mesh(
        np.asarray(jax.devices()[:n], dtype=object).reshape(shape), names
    )
    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        param_sharding_rules(config, mesh),
        is_leaf=lambda x: isinstance(x, P),
    )
    placed = jax.device_put(params, shardings)
    sharded = jax.jit(
        lambda p, i, m: forward(p, config, i, m, use_flash=True, mesh=mesh)
    )(placed, ids, mask)
    assert float(np.max(np.abs(np.asarray(sharded) - np.asarray(dense)))) < 5e-3


@pytest.mark.parametrize(
    "shape,names", [((4,), ("dp",)), ((4, 2), ("dp", "tp")), ((8,), ("knn",))]
)
def test_fused_segment_attention_runs_per_device_under_a_mesh(shape, names):
    """The packed path's kernel is a Mosaic kernel too: under a mesh
    forward(seg=...) runs it inside shard_map, slab rows over 'dp' where
    there is such an axis; the pooled vectors must be the dense path's."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from pathway_tpu.models.transformer import (
        TransformerConfig,
        forward,
        init_params,
        param_sharding_rules,
    )

    n = int(np.prod(shape))
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices (conftest emulates 8)")
    config = TransformerConfig(
        vocab_size=512, hidden=128, layers=1, heads=4, mlp_dim=128, max_len=64
    )
    params = init_params(jax.random.PRNGKey(0), config)
    rng = np.random.default_rng(0)
    ids = rng.integers(4, 512, size=(8, 40)).astype(np.int32)
    seg = np.ones_like(ids)
    seg[:, 17:] = 2
    seg[3, 29:] = 0  # trailing padding
    seg[5] = 0  # a replica's filler row
    dense = forward(
        params, config, ids, None, seg=seg, max_segments=2, use_flash=False
    )

    mesh = Mesh(
        np.asarray(jax.devices()[:n], dtype=object).reshape(shape), names
    )
    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        param_sharding_rules(config, mesh),
        is_leaf=lambda x: isinstance(x, P),
    )
    placed = jax.device_put(params, shardings)
    sharded = jax.jit(
        lambda p, i, s: forward(
            p, config, i, None, seg=s, max_segments=2, use_flash=True,
            mesh=mesh,
        )
    )(placed, ids, seg)
    assert np.isfinite(np.asarray(sharded)).all()
    assert float(np.max(np.abs(np.asarray(sharded) - np.asarray(dense)))) < 5e-3
