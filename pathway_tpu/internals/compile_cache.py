"""Where compiled XLA programs are kept between processes.

A fresh process compiles every program it runs (the fused ingest, one
fused search per query bucket, the packed-slab encoder, the index
scatter); JAX's persistent compilation cache lets the next process load
them instead.  The directory is part of the cache key, so it must not
move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself — nothing is
    done here, and no other directory is ever set in code;
  * unset: a fixed path inside the checkout, ``<repo>/.jax_cache``
    (listed in ``.gitignore``).

Either way every program is kept, not only the slow ones: the main path
compiles some hundred small programs (scatters, slices, probes) beside
the few large ones, and with jax's default one-second threshold they made
up half of a warm start's compile time (chip run, PR 21).

Entry points (``chip_smoke.py``, ``chipbench``) call
``configure()`` before their first compile.  This module imports jax only
inside ``configure()``, so ``import pathway_tpu`` stays jax-free.

What compiling costs is measured here too.  ``observe()`` (called by
``configure()`` and by every device pipeline's start) listens to jax's own
monitoring events and folds them into the span record
(``internals/tracing.py``): ``compile.trace``, ``compile.lower``,
``compile.backend`` (a cache look-up is inside it) and
``compile.cache_load`` as count and seconds, ``compile.cache_hits`` and
``compile.cache_misses`` as counts; and, by program, a bounded table and
a ring of the recent backend compilations with the span that was open on
the compiling thread: ``/status`` "compile" (``compile_status()``).  The
phases run on whichever thread compiles, several at once, so their
seconds are thread-seconds.  jax reports a jitted function traced inside
another's trace by itself (every `jnp` function is one): the record's
``compile.trace`` counts each second once, with the outermost trace,
while a program's row keeps jax's figure, so that the row of
`_fwd_packed` says what tracing `_fwd_packed` costs.  jax reports such a
function at every call inside a trace, one it has traced before in
microseconds: the row of a Pallas kernel (`ops.kernels.kernel_call` names
it) counts the layers' calls, and `wrapped`, every `pallas_call`'s
wrapper, the kernels' real traces.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List

from pathway_tpu.internals import tracing

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# jax's duration event -> the span record's name; a program's row holds
# count and seconds of each, in this order
PHASES = {
    TRACE_EVENT: "compile.trace",
    LOWER_EVENT: "compile.lower",
    BACKEND_EVENT: "compile.backend",
    CACHE_LOAD_EVENT: "compile.cache_load",
}
# where a phase's count stands in a program's row (its seconds follow it)
COLUMN = {name: 2 * i for i, name in enumerate(PHASES.values())}
COUNTERS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}
PROGRAMS_KEPT = 256  # rows of a thread's table; later programs go to "other"
PROGRAMS_SERVED = 16  # rows of /status "compile"."programs"
RECENT_KEPT = 64  # backend compilations of /status "compile"."recent"


def default_dir() -> str:
    """The fixed in-checkout cache path: ``.jax_cache`` beside the
    ``pathway_tpu`` package directory."""
    package_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package_dir), ".jax_cache")


def cache_dir() -> str:
    """The directory the cache lives in for this process."""
    return os.environ.get(ENV_VAR) or default_dir()


def configure() -> str:
    """Point JAX's persistent compilation cache at ``cache_dir()`` and
    return it.  Call before the first compile of the process: JAX decides
    whether the cache is in use the first time it compiles."""
    import jax

    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    observe()
    return path


# -- what compiling costs -------------------------------------------------------


class _ThreadCompiles:
    """One thread's side, so that an event takes no lock (as the span
    record's totals): its table by program, how many traces are open on
    it, and the cache load heard since its last backend compilation."""

    __slots__ = ("thread", "programs", "open_traces", "loaded")

    def __init__(self) -> None:
        self.thread = threading.current_thread()
        self.programs: Dict[str, list] = {}
        self.open_traces = 0  # traces open on this thread, one inside another
        self.loaded = None


def _fold(into: Dict[str, list], programs: Dict[str, list]) -> None:
    for program, row in list(programs.items()):
        acc = into.setdefault(program, [0, 0.0] * len(PHASES))
        for i, value in enumerate(row):
            acc[i] += value


class CompileRecord:
    """Every thread's table by program and the ring of recent backend
    compilations."""

    def __init__(self) -> None:
        self.lock = threading.Lock()  # the list of threads
        self.threads: List[_ThreadCompiles] = []
        self.retired: Dict[str, list] = {}  # tables of threads that ended
        self.recent: deque = deque(maxlen=RECENT_KEPT)
        self.local = threading.local()

    def here(self) -> _ThreadCompiles:
        try:
            return self.local.compiles
        except AttributeError:
            mine = self.local.compiles = _ThreadCompiles()
            with self.lock:
                if len(self.threads) >= 64:
                    for ended in [t for t in self.threads if not t.thread.is_alive()]:
                        _fold(self.retired, ended.programs)
                        self.threads.remove(ended)
                self.threads.append(mine)
            return mine

    def programs(self) -> Dict[str, list]:
        with self.lock:
            out = {program: list(row) for program, row in self.retired.items()}
            threads = list(self.threads)
        for t in threads:
            _fold(out, t.programs)
        return out


_RECORD = CompileRecord()
_observing = False
_observe_lock = threading.Lock()


def reset_compiles() -> CompileRecord:
    """A fresh table and ring (tests scope a record to one scenario)."""
    global _RECORD
    _RECORD = CompileRecord()
    return _RECORD


def program_name(fun_name: str) -> str:
    """jax names a trace by the function (`_fwd_packed`) and a lowering
    or a compilation by the module (`jit(_fwd_packed)`; the device's
    profile says `jit__fwd_packed`): one row for all."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name[4:] if fun_name.startswith("jit_") else fun_name


def _on_duration(event: str, seconds: float, fun_name: str = "", **_kw) -> None:
    name = PHASES.get(event)
    if name is None:
        return
    mine = _RECORD.here()
    if event == CACHE_LOAD_EVENT:
        # it names no program: the backend compilation it is inside of
        # ends next on this thread, and takes it
        tracing.add(name, seconds)
        mine.loaded = seconds
        return
    if event == TRACE_EVENT:
        # the record counts every second once, with the outermost trace,
        # whose seconds hold those of the functions traced inside it; a
        # program's row keeps jax's figure
        mine.open_traces = max(0, mine.open_traces - 1)
        tracing.add(name, seconds if mine.open_traces == 0 else 0.0)
    else:
        tracing.add(name, seconds)
    program = program_name(fun_name)
    row = mine.programs.get(program)
    if row is None:
        kept = program if len(mine.programs) < PROGRAMS_KEPT else "other"
        row = mine.programs.setdefault(kept, [0, 0.0] * len(PHASES))
    row[COLUMN[name]] += 1
    row[COLUMN[name] + 1] += seconds
    if event != BACKEND_EVENT:
        return
    loaded, mine.loaded = mine.loaded, None
    if loaded is not None:
        row[COLUMN["compile.cache_load"]] += 1
        row[COLUMN["compile.cache_load"] + 1] += loaded
    under = tracing.current_span()
    _RECORD.recent.append({
        "monotonic_s": time.monotonic(),
        "program": program,
        "seconds": seconds,
        "cache_load_s": loaded,  # None: compiled, not loaded
        "thread": mine.thread.name,
        "span": under.name if under is not None else None,
        "seq": under.seq if under is not None else None,
        "epoch": under.epoch if under is not None else None,
    })


def _on_scalar(event: str, _value, **_kw) -> None:
    """jax says that a phase begins as a scalar, its start time: how the
    traces inside a trace are told from the ones after it."""
    if event == TRACE_EVENT:
        _RECORD.here().open_traces += 1


def _on_event(event: str, **_kw) -> None:
    name = COUNTERS.get(event)
    if name is not None:
        tracing.add(name)


def observe() -> bool:
    """Start listening to jax's compile events, once a process; True if
    listening.  Never imports jax: the module is taken from `sys.modules`
    once something else loaded it, and without it nothing is done (the
    connector and the engine stay jax-free)."""
    global _observing
    monitoring = sys.modules.get("jax.monitoring")
    if monitoring is None:
        return False
    with _observe_lock:
        if not _observing:
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
            monitoring.register_scalar_listener(_on_scalar)
            # a name that has counted nothing yet reads 0, not absent
            for name in (*PHASES.values(), *COUNTERS.values()):
                tracing.add(name, n=0)
            _observing = True
    return True


def compile_status() -> Dict[str, Any]:
    """The `"compile"` key of /status: `programs`, the programs with the
    most seconds (trace, lowering, backend compilation and cache load:
    count and seconds each), and `recent`, the last backend compilations,
    oldest first, each with the program's clock, the thread, and the span
    that was open on it: a recompilation in service reads "`_fwd_packed`
    under `pipeline.launch` seq 1204"."""
    rows = sorted(
        _RECORD.programs().items(), key=lambda kv: -sum(kv[1][1::2])
    )[:PROGRAMS_SERVED]
    return {
        "programs": [
            {
                "program": program,
                **{
                    name.split(".", 1)[1]: {"count": row[i], "total_s": row[i + 1]}
                    for name, i in COLUMN.items()
                },
            }
            for program, row in rows
        ],
        "recent": list(_RECORD.recent),
    }
