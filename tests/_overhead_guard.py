"""The measuring half of test_perf_smoke.py::
test_observability_overhead_under_5pct, run as a script in a child
interpreter: prints {"metrics": ratio, "metrics_and_span_record": ratio},
each the median over PAIRS paired blocks of the instrumented engine's CPU
time over the bare engine's.

A child of its own because the reading has a part that belongs to the
process and not to the code: with another string-hash seed (and so
another layout of every dict the loops touch) the same tree reads 1.5
points apart, steadily for as long as the process lives.  The test runs
one child a seed and judges the median.
"""

from __future__ import annotations

import gc
import json
import os
import statistics
import sys
from time import perf_counter, thread_time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pathway_tpu.engine.engine import Engine, InputQueueSource, RowwiseNode
from pathway_tpu.engine.value import ref_scalar
from pathway_tpu.internals import device_pipeline, tracing

ROWS, BLOCK, PAIRS = 512, 10, 40
DELTAS = [(ref_scalar("k", i), (i,), 1) for i in range(ROWS)]


class _NoTracing:
    """Stands in for internals/tracing.py inside device_pipeline.py in the
    bare arm: the span record has no switch, so the guard is where it is
    taken out."""

    class span:
        def __init__(self, *_args, **_kwargs):
            self.rows = 0

        def __enter__(self):
            return self

        def __exit__(self, *_exc):
            self.t1 = perf_counter()  # the pipeline reads a launch's end
            return False

        def cancel(self):
            pass

    @staticmethod
    def record(*_args, **_kwargs):
        pass

    @staticmethod
    def current_epoch():
        return None

    @staticmethod
    def mark(_name):
        pass


def _ident(keys, cols):
    return cols[0]


class Arm:
    """source -> 3 rowwise maps, and with `pipeline` one batch a tick
    handed to a DevicePipeline whose three callbacks do nothing."""

    def __init__(self, instrumented: bool, pipeline: bool):
        self.tracing = tracing if instrumented else _NoTracing
        self.eng = Engine(metrics=instrumented)
        self.src = InputQueueSource(self.eng)
        node = self.src
        for _ in range(3):
            node = RowwiseNode(self.eng, [node], _ident)
        self.time = 2
        self.pipe = None
        if pipeline:
            self.pipe = device_pipeline.DevicePipeline(
                lambda item: (item, {"rows": ROWS}),
                lambda payload: None,
                wait=lambda handle: None,
                name="overhead-guard",
                # room for a whole block: a submit that found the queue
                # full would wait for the other threads inside the timed
                # loop, and that wait is the machine's, not the record's
                max_prepared=BLOCK,
            )

    def block(self) -> float:
        """CPU seconds the engine's thread spends on BLOCK ticks."""
        device_pipeline.tracing = self.tracing
        t0 = thread_time()
        for _ in range(BLOCK):
            self.src.push(self.time, DELTAS)
            self.eng.process_time(self.time)
            if self.pipe is not None:
                self.pipe.submit(self.time)
            self.time += 2
        spent = thread_time() - t0
        if self.pipe is not None:
            self.pipe.drain()  # the other side starts with it idle
        return spent

    def close(self) -> None:
        self.eng._gc_unfreeze()
        if self.pipe is not None:
            self.pipe.close()


def measure(pipeline: bool) -> float:
    on, off = Arm(True, pipeline), Arm(False, pipeline)
    try:
        on.block(), off.block()  # warmup (allocators, bytecode caches)
        ratios = []
        for pair in range(PAIRS):
            if pair % 2:
                a = on.block()
                b = off.block()
            else:
                b = off.block()
                a = on.block()
            ratios.append(a / b)
        return statistics.median(ratios)
    finally:
        on.close()
        off.close()
        device_pipeline.tracing = tracing


if __name__ == "__main__":
    # a deployment has jax loaded, and a span then tests the profiler's
    # flag: the guard pays for that too
    import jax.profiler  # noqa: F401

    # threshold-triggered collections would bill the whole heap's scan to
    # whichever arm allocates the triggering object (the engines still
    # collect on their own cadence, `_gc_pulse`)
    gc.collect()
    gc.disable()
    # the clock is the engine thread's: the pipeline's threads take their
    # turn where the engine waits for them (`drain`, after each block),
    # not by forcing it off the interpreter lock in the middle of one,
    # which costs it the more the busier the machine is
    sys.setswitchinterval(1.0)
    print(json.dumps({
        "metrics": measure(False),
        "metrics_and_span_record": measure(True),
    }))
