"""The collector policy of a run (engine/collector.py): the same for
`run_static` and the streaming run.  All on the CPU."""

from __future__ import annotations

import gc
import threading
import time
import weakref

import pytest

import pathway_tpu as pw
from pathway_tpu.engine import collector
from pathway_tpu.internals import tracing

MODES = ("static", "streaming")


@pytest.fixture()
def record():
    return tracing.reset_spans()


@pytest.fixture(autouse=True)
def _collector_as_found():
    was_enabled = gc.isenabled()
    yield
    (gc.enable if was_enabled else gc.disable)()


def _count(name: str) -> int:
    return tracing.spans_status()["totals"].get(name, {"count": 0})["count"]


class _Schema(pw.Schema):
    x: int


def _run(mode: str, on_change, *, rows: int = 3, inside=None):
    """One run of `rows` ticks, one row a tick, with `on_change`
    subscribed; `inside` runs on the connector's thread before the stream
    closes (streaming)."""
    if mode == "static":
        lines = "\n".join(f"{i} | {2 * i + 2}" for i in range(rows))
        table = pw.debug.table_from_markdown("x | __time__\n" + lines)
        seen = on_change
    else:
        ticked = threading.Event()

        def seen(*a, **k):
            try:
                on_change(*a, **k)
            finally:
                ticked.set()

        class Subject(pw.io.python.ConnectorSubject):
            def run(self):
                for i in range(rows):
                    ticked.clear()
                    self.next(x=i)
                    self.commit()
                    ticked.wait(10)  # the next row is the next tick's
                    time.sleep(0.001)
                if inside is not None:
                    inside()

        table = pw.io.python.read(Subject(), schema=_Schema)
    pw.io.subscribe(table, on_change=seen)
    pw.run(monitoring_level=None, autocommit_duration_ms=10)


@pytest.mark.parametrize("mode", MODES)
def test_automatic_collection_is_off_inside_a_run_and_back_after(mode):
    gc.enable()
    seen = []
    _run(mode, lambda *a, **k: seen.append(gc.isenabled()))
    assert seen and not any(seen)
    assert gc.isenabled()


@pytest.mark.parametrize("mode", MODES)
def test_automatic_collection_is_back_after_an_exception(mode, monkeypatch):
    monkeypatch.setattr(collector, "FLOOR_S", 0.0)  # every tick freezes
    gc.enable()
    seen = []

    def on_change(*a, **k):
        seen.append(gc.isenabled())
        if len(seen) == 2:
            raise RuntimeError("mid-run")

    with pytest.raises(RuntimeError, match="mid-run"):
        _run(mode, on_change)
    assert seen == [False, False]
    assert gc.isenabled()
    assert gc.get_freeze_count() == 0


@pytest.mark.parametrize("mode", MODES)
def test_a_caller_who_had_the_collector_off_finds_it_off(mode):
    gc.disable()
    seen = []
    _run(mode, lambda *a, **k: seen.append(gc.isenabled()))
    assert seen and not any(seen)
    assert not gc.isenabled()


@pytest.mark.parametrize("mode", MODES)
def test_the_heap_is_frozen_during_a_run_and_not_after(mode, monkeypatch):
    monkeypatch.setattr(collector, "FLOOR_S", 0.0)
    frozen = []
    _run(mode, lambda *a, **k: frozen.append(gc.get_freeze_count()))
    # a pulse follows every tick, so from the second tick on (streaming:
    # from the first, the start of streaming froze what start-up built)
    assert len(frozen) == 3 and frozen[-1] > 0
    assert gc.get_freeze_count() == 0  # finish() unfroze


def test_no_automatic_collection_over_a_few_hundred_ticks(record, monkeypatch):
    monkeypatch.setattr(collector, "FLOOR_S", 0.01)
    gc.enable()
    pulses = []
    _run("streaming", lambda *a, **k: pulses.append(_count("gc.pulses")), rows=300)
    assert _count("engine.tick") >= 300
    assert _count("gc.automatic") == 0
    assert "gc.automatic" in tracing.spans_status()["totals"]  # it reads 0, not nothing
    assert pulses[0] >= 1  # the start of streaming
    assert pulses[-1] >= pulses[0] + 5 and _count("gc.pulses") >= pulses[-1]
    assert _count("gc.frozen_objects") > 0
    # every pulse is a collection of generation 1 or 2, a `host.gc` span
    assert len([ev for ev in record.ring if ev[0] == "host.gc"]) >= _count("gc.pulses")


def test_a_pulse_is_due_by_what_was_allocated(record, monkeypatch):
    monkeypatch.setattr(collector, "YOUNG_LIMIT", 1000)
    monkeypatch.setattr(collector, "FLOOR_S", 3600.0)
    kept = []

    def on_change(*a, **k):
        kept.append([[] for _ in range(2000)])  # tracked, alive, young

    _run("streaming", on_change, rows=4)
    assert _count("gc.pulses") >= 1 + 3  # the start, then the ticks' own
    assert _count("gc.automatic") == 0


def test_a_pulse_fires_on_the_time_floor_with_no_ticks_at_all(record, monkeypatch):
    monkeypatch.setattr(collector, "FLOOR_S", 0.1)
    _run("streaming", lambda *a, **k: None, rows=0, inside=lambda: time.sleep(0.8))
    assert _count("engine.tick") <= 2  # time 0 and the end: no data came
    assert _count("gc.pulses") >= 1 + 4
    assert _count("gc.automatic") == 0


def test_a_cycle_of_another_thread_is_collected_by_a_later_pulse(record, monkeypatch):
    monkeypatch.setattr(collector, "FLOOR_S", 0.05)
    gc.enable()
    seen = {}

    class Node:
        pass

    def inside():
        seen["thread"] = threading.current_thread().name
        seen["enabled"] = gc.isenabled()
        a, b = Node(), Node()
        a.other, b.other = b, a
        ref = weakref.ref(a)
        del a, b
        seen["alive_at_first"] = ref() is not None
        deadline = time.monotonic() + 10
        while ref() is not None and time.monotonic() < deadline:
            time.sleep(0.02)
        seen["collected"] = ref() is None

    _run("streaming", lambda *a, **k: None, rows=1, inside=inside)
    assert seen["thread"] != threading.current_thread().name
    assert seen == {"thread": seen["thread"], "enabled": False,
                    "alive_at_first": True, "collected": True}
    assert _count("gc.automatic") == 0


def test_two_runs_at_once_restore_the_collector_when_the_last_ends():
    gc.enable()
    entered, inner_done, release = (threading.Event() for _ in range(3))
    seen = {}

    def long_run():
        with collector.POLICY.run():
            entered.set()
            inner_done.wait(10)
            seen["while_other_ended"] = gc.isenabled()
            release.wait(10)

    t = threading.Thread(target=long_run)
    t.start()
    try:
        assert entered.wait(10)
        with collector.POLICY.run():
            seen["inside"] = gc.isenabled()
        inner_done.set()
        time.sleep(0.05)
        seen["one_left"] = gc.isenabled()
    finally:
        inner_done.set()
        release.set()
        t.join(10)
    assert not t.is_alive()
    assert seen == {"inside": False, "while_other_ended": False, "one_left": False}
    assert gc.isenabled()
