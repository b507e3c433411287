"""Pallas TPU kernels for the data-plane hot ops.

The reference's hot loops are CPU-side Rust: per-worker ndarray matmul+top-k
KNN (src/external_integration/brute_force_knn_integration.rs:52-110) and
torch models behind UDFs (xpacks/llm/embedders.py:342, llms.py:456). Here the
same roles are filled by hand-written Pallas kernels that fuse work into
single VMEM-resident passes:

  * flash_attention — online-softmax blocked attention (encoder + causal
    decoder), O(L) memory instead of the [L, L] score matrix;
  * knn_block_topk — streaming similarity + per-block top-k, never
    materializing the [Q, N] score matrix in HBM;
  * segment_attention (its module) — the packed ingest path's attention:
    one slab row per grid step, segment mask, softmax and p @ v in VMEM,
    q/k/v read in place from the QKV matmul's output.

Every kernel runs `interpret=True` off-TPU so the CPU test mesh exercises
identical code paths.
"""

from pathway_tpu.ops.kernels.flash_attention import flash_attention
from pathway_tpu.ops.kernels.knn_topk import knn_topk

__all__ = ["flash_attention", "knn_topk"]


def on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"
