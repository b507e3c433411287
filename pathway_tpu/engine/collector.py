"""The collector policy of a run: one for `Engine.run_static` and the
streaming run (`io/_connector_runtime.py`).

CPython's cyclic collector is triggered by allocation counts, on whichever
thread allocates the object that crosses a threshold, and a full
collection walks every tracked object alive: imports, parameters' pytrees,
compile caches, the index's key maps.  Engine state is acyclic but
tracked, so a run that builds millions of tuples pays for rescans of a
heap that never dies, with the interpreter lock held throughout.  Inside a
run the policy therefore

- turns the threshold-triggered collector off (`run()`), and back on at
  every way out iff it was the one to turn it off;
- collects the young objects itself, between ticks, on the engine's thread
  (`pulse()`): `gc.collect(1)` then `gc.freeze()`, so that what survived
  is in the permanent generation and no later collection walks it again.
  With the automatic collector off `gc.get_count()[0]` is the number of
  tracked objects allocated and not freed since the last collection — all
  a pulse has to walk — so a pulse is due when that count passes
  `YOUNG_LIMIT` or `FLOOR_S` have gone by, whichever comes first: a busy
  run pays by what it allocates, an idle one still reclaims the cycles of
  other threads (REST handlers, prep threads) and jax's deferred frees;
- every `FULL_EVERY_S` unfreezes and collects everything once (`full`),
  which reclaims cycles that were frozen alive and died later; the
  streaming run does the same once before its first streamed batch, so
  that the heap start-up built is frozen before any pulse;
- unfreezes when a run ends (`unfreeze()`), so that repeated runs in one
  process pin no garbage.

The state is the process's, as the collector is: thread workers run
several engines at once, and a server's run may live on a daemon thread
beside another run.  Counters in the span record (`/status` "spans"):
`gc.pulses` (count, and `total_s` spent in them), `gc.automatic`
(collections that started outside a pulse while a run had the collector
off: 0 says the policy engaged), `gc.frozen_objects` (`count` is
`gc.get_freeze_count()` after the last full pulse plus what each pulse
since found alive and froze: the exact count walks the whole permanent
generation, 15 ms for 150,000 objects on the sandbox's CPU, and a pulse
must cost by what is young)."""

from __future__ import annotations

import gc
import threading
import time as time_mod
from contextlib import contextmanager

from pathway_tpu.internals import tracing

YOUNG_LIMIT = 100_000  # tracked objects alive since the last collection
FLOOR_S = 1.0  # a pulse at least this often while ticks or flushes come
FULL_EVERY_S = 300.0  # the unfreeze-and-collect of everything

_monotonic = time_mod.monotonic
_get_count = gc.get_count


class CollectorPolicy:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._runs = 0
        self._disabled = False  # this policy turned the collector off
        self._frozen = 0  # what gc.frozen_objects counts so far
        self._last_pulse = self._last_full = _monotonic()

    @contextmanager
    def run(self):
        """Around a run, static or streaming."""
        with self._lock:
            self._runs += 1
            if self._runs == 1:
                # a run is as old as its start, whatever ran before it
                self._last_pulse = self._last_full = _monotonic()
                if gc.isenabled():
                    self._disabled = True
                    gc.disable()
                tracing.add("gc.automatic", n=0)
                gc.callbacks.append(self._watch)
        try:
            yield
        finally:
            with self._lock:
                self._runs -= 1
                if self._runs == 0:
                    gc.callbacks.remove(self._watch)
                    self.unfreeze()
                    if self._disabled:
                        self._disabled = False
                        gc.enable()

    def _watch(self, phase: str, info: dict) -> None:
        # a pulse holds the lock, and no other collection starts during one
        if phase == "start" and not self._lock.locked():
            tracing.add("gc.automatic")

    def pulse(self, full: bool = False) -> None:
        """Called where the engine's thread holds no half-built batch: the
        end of a tick, the end of a flush."""
        now = _monotonic()
        young = _get_count()[0]
        if not full:
            if young < YOUNG_LIMIT and now - self._last_pulse < FLOOR_S:
                return
            full = now - self._last_full >= FULL_EVERY_S
        if not self._lock.acquire(blocking=False):
            return  # another engine's thread is at it
        try:
            if full:
                gc.unfreeze()
                gc.collect()
                gc.freeze()
                # exact, and a walk of the permanent generation: only
                # where everything has just been walked anyway
                frozen = gc.get_freeze_count()
                self._last_full = now
            else:
                frozen = self._frozen + max(0, young - gc.collect(1))
                gc.freeze()
            self._last_pulse = done = _monotonic()
            tracing.add("gc.pulses", done - now)
            tracing.add("gc.frozen_objects", n=frozen - self._frozen)
            self._frozen = frozen
        finally:
            self._lock.release()

    unfreeze = staticmethod(gc.unfreeze)


POLICY = CollectorPolicy()
