"""Breaks the timed path underneath the harness, then drives a run.

  python -m chipbench.tests.faults <fault> <chipbench.run arguments>

Each fault is one a cell of this benchmark can have; the run that follows
must come out with "correct": false.
"""

import sys


def state_unchanged():
    """The index scatter returns its state unchanged."""
    import jax

    from pathway_tpu.ops import knn

    knn._compiled_update = lambda: jax.jit(
        lambda buffer, valid, slots, vectors, slot_valid: (buffer, valid)
    )


def half_batch():
    """Half of every ingest batch is left out of the index."""
    from pathway_tpu.ops.knn import DeviceKnnIndex

    whole = DeviceKnnIndex.add_batch

    def add_half(self, keys, vectors, shards=None):
        keys = list(keys)
        n = len(keys) // 2
        return whole(
            self, keys[:n], vectors[:n], None if shards is None else shards[:n]
        )

    DeviceKnnIndex.add_batch = add_half


def altered_answer():
    """A score is altered where the answer is produced."""
    from pathway_tpu.xpacks.llm import document_store

    pack = document_store._pack_retrieval_results

    def bent(texts, metas, scores):
        return pack(texts, metas, [s + 0.01 for s in scores or ()])

    document_store._pack_retrieval_results = bent


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch, altered_answer)}

if __name__ == "__main__":
    FAULTS[sys.argv[1]]()
    from chipbench import run

    sys.exit(run.main(sys.argv[2:]))
