"""Pallas TPU kernels for the data-plane hot ops.

The reference's hot loops are CPU-side Rust: per-worker ndarray matmul+top-k
KNN (src/external_integration/brute_force_knn_integration.rs:52-110) and
torch models behind UDFs (xpacks/llm/embedders.py:342, llms.py:456). Here the
same roles are filled by hand-written Pallas kernels that fuse work into
single VMEM-resident passes.  The ones a packed ingest trunk runs, a module
each, named as the device's profile names them:

  * `segment_attention` — the encoders' (`models/transformer.py`): one slab
    row a grid step, segment mask, softmax and p @ v in VMEM, q/k/v read in
    place from the QKV matmul's output;
  * `mla_segment_attention` (`mla_attention.py`) — its causal sibling for
    latent attention's two-part heads (`models/mla.py`, which
    `models/moe_mla.py` and `models/longcat.py` run);
  * `eva_attention`, `eva_pool_chunks`, `eva_rope` (`eva_attention.py`) —
    chunked linear attention over rows of thousands of slots, the chunks'
    summaries and RoPE where the matmuls left q and k (`models/eva.py`);
  * `hybrid_attention_window` / `hybrid_attention_global`, `hybrid_rope`
    (`hybrid_attention.py`) — one grouped-query kernel for sliding-window
    and global layers, and its RoPE (`models/moe_hybrid.py`); for heads of
    one 128-wide operand (the rotated dims inside) the same kernel runs as
    `laguna_attention_window` / `laguna_attention_global`;
  * `cca_attention` (`cca_attention.py`) — `mla_segment_attention`'s
    sibling for one-part heads that share key/value heads, the group's
    query heads one product a step (`models/zaya.py`);
  * `cca_latent` (`cca_latent.py`) — that kernel's three operands from the
    fused projection's output in one pass over a slab row: group means,
    two causal convolutions cut at the packed documents' seams,
    normalisation, temperature, partial RoPE, the value shift
    (`models/zaya.py`).

Beside them, on no packed path: `flash_attention` (online-softmax blocked
attention, O(L) memory instead of the [L, L] score matrix) and
`knn_block_topk` (streaming similarity + per-block top-k, never
materializing the [Q, N] score matrix in HBM).

**Once a program, not once a layer.**  Every kernel of the first list is
called through `kernel_call`: ONE `jax.jit` a kernel a set of static
parameters, under the kernel's own name.  A bare `pl.pallas_call` is a new
function at every call, so a trunk of N layers traces the wrapper and the
body and lowers them N times, in every program that holds the trunk, compile
cache or not: 98 such traces took 29 s of EvaByte's 62 s warm start and 42
took 10.7 s of MiMo-V2.5's 44 s.  Through one jitted function jax traces a
kernel once for each shape it meets and lowers it to one `func.func` that
the layers call (8 traces, 5 s of 32, and 10, 2 s of 34: PERF.md section 6,
PR 41); XLA inlines the calls, so the compiled program is the bare call's.

Every kernel runs `interpret=True` off-TPU so the CPU test mesh exercises
identical code paths.
"""

from __future__ import annotations

import functools

from pathway_tpu.ops.kernels.flash_attention import flash_attention
from pathway_tpu.ops.kernels.knn_topk import knn_topk

__all__ = ["flash_attention", "knn_topk"]


def on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def kernel_call(name: str, impl, *, interpret=None, **statics):
    """`impl(*arrays, interpret=, **statics)` as a jitted function named
    `name`: the SAME function every time it is asked for with the same
    `impl` and statics (tiles, head counts, `window`, `scale`: whatever the
    kernel's `pallas_call` is built from besides its arguments' shapes;
    hashable).  Arrays, and a `None` in an array's place, are the call's
    arguments.  `interpret` None: off the TPU.  See the module's note for
    why no kernel a trunk runs is called bare; `name` is the kernel's
    `pallas_call` name, so `/status` "compile"."programs" has a row a
    kernel."""
    if interpret is None:
        interpret = not on_tpu()
    return _jitted(name, impl, interpret=bool(interpret), **statics)


@functools.lru_cache(maxsize=None)
def _jitted(name: str, impl, **statics):
    import jax

    call = functools.partial(impl, **statics)
    # jax names the trace, and the function the layers call, by these
    call.__name__ = call.__qualname__ = name
    return jax.jit(call)
