"""Every Pallas kernel a packed trunk runs is called through
`ops.kernels.kernel_call`: one jitted function a kernel a set of static
parameters, so a program of N layers traces the kernel's wrapper and body
and lowers them once, not N times (a warm start's `setup.trace_lower_s`).
Interpreted on the CPU at tiny shapes; counts and texts, no clock."""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.internals import compile_cache
from pathway_tpu.ops import kernels
from pathway_tpu.ops.kernels import cca_attention as cca_k
from pathway_tpu.ops.kernels import cca_latent as latent_k
from pathway_tpu.ops.kernels import eva_attention as eva_k
from pathway_tpu.ops.kernels import hybrid_attention as hybrid_k
from pathway_tpu.ops.kernels import mla_attention as mla_k
from pathway_tpu.ops.kernels import segment_attention as seg_k

LAYERS = 4


class Site(NamedTuple):
    module: object
    body: str  # the kernel's body, entered once a trace of its `pallas_call`
    operands: Callable  # () -> the arrays of a call
    through: Callable  # (*operands, **statics): the public function
    bare: Callable  # the same call of the function that applies the `pallas_call`
    statics: dict  # of the program's layers
    other: dict  # a second set
    other_name: str = ""  # the second set's kernel, where it has another name


def _segment_operands():
    rng = np.random.default_rng(0)
    qkv = jnp.asarray(rng.normal(size=(2, 32, 3 * 128)), jnp.float32)
    seg = jnp.asarray(np.r_[[1] * 20, [2] * 9, [0] * 3][None].repeat(2, 0), jnp.int32)
    return qkv, seg


def _mla_operands():
    from tests.test_moe_mla import _mla_operands

    seg = jnp.asarray(np.r_[[1] * 9, [2] * 31][None], jnp.int32)
    return (*_mla_operands(1, 40, 4, "float32"), seg)


EVA_TILES = dict(block=32, summary_tile=16)


def _eva_operands():
    from pathway_tpu.models import eva
    from tests.test_eva import _operands, _slab

    q, k, v, layer = _operands(1, 128)
    layout = eva_k.window_layout(jnp.asarray(_slab([[70, 37]], 128)), 32, 4, **EVA_TILES)
    kbar, vbar = eva.chunk_summaries(k, v, layer, layout, eva.TINY)
    names = ("code", "kind", "key_lo", "chunk_code", "sum_kind", "sum_lo", "sum_hi")
    return q, k, v, kbar, vbar, {name: layout[name] for name in names}


def _pool_operands():
    """What `chunk_summaries` hands the kernel."""
    from pathway_tpu.models import eva
    from tests.test_eva import _operands, _slab

    _, k, v, layer = _operands(1, 128)
    layout = eva_k.window_layout(jnp.asarray(_slab([[70, 37]], 128)), 32, 4, **EVA_TILES)
    handed = []
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(eva_k, "pool_chunks", lambda *a: handed.append(a) or a[:2])
        eva.chunk_summaries(k, v, layer, layout, eva.TINY, fused=True)
    return handed[0]


def _rope_operands(width: int):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 128, width)), jnp.float32)
    angle = jnp.asarray(rng.uniform(0, 6.0, size=(2, 128, 128)), jnp.float32)
    return x, jnp.cos(angle), jnp.sin(angle)


def _hybrid_operands():
    from pathway_tpu.models.trunk import packed_positions
    from tests.test_moe_hybrid import _operands, _packed_seg

    seg = _packed_seg(128, [[77, 40]])
    pos = packed_positions(seg)
    sink = jnp.asarray(np.random.default_rng(1).normal(size=4), jnp.float32)
    lo = hybrid_k.key_lo(seg, pos, 32)  # a global layer's; a window layer's steps need none
    return (*_operands(1, 128, 4, 2, jnp.float32), seg, lo, sink)


def _hybrid_through(*operands, window):
    *arrays, lo, sink = operands
    return hybrid_k.hybrid_attention(
        *arrays, lo if window is None else None, kv_heads=2, window=window, sink=sink,
        block=32, interpret=True)


def _hybrid_bare(*operands, window):
    *arrays, lo, sink = operands
    return hybrid_k._attend(
        *arrays, lo if window is None else None, sink, kv_heads=2, window=window, block=32,
        interpret=True)


def _cca_operands():
    rng = np.random.default_rng(3)
    drawn = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    seg = jnp.asarray(np.r_[[1] * 9, [2] * 27, [0] * 4][None], jnp.int32)
    return drawn(1, 40, 4 * 128), drawn(1, 40, 2 * 128), drawn(1, 40, 2 * 128), seg


LATENT_LEAVES = ("conv0_w", "conv0_b", "conv1_w", "conv1_b", "tau")


def _latent_operands():
    """The projection's output for 4 query over 2 key/value heads, a packed
    row, RoPE's tables and a layer's five leaves of the latent."""
    from pathway_tpu.models.trunk import packed_positions
    from pathway_tpu.ops.kernels.hybrid_attention import rope_tables

    rng = np.random.default_rng(4)
    drawn = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    seg = jnp.asarray(np.r_[[1] * 9, [2] * 27, [0] * 4][None], jnp.int32)
    leaves = (drawn(2, 6 * 128), drawn(6 * 128), drawn(6, 2 * 128, 128) * 0.06,
              drawn(6 * 128), 1.5 + 0.25 * drawn(2))
    return (drawn(1, 40, 8 * 128), seg, *rope_tables(packed_positions(seg), 5e6), *leaves)


def _latent_through(qkv, seg, cos, sin, *leaves, **statics):
    return latent_k.cca_latent(
        qkv, seg, (cos, sin), dict(zip(LATENT_LEAVES, leaves)), heads=4, kv_heads=2, **statics)


SITES = {
    # these two are built from their operands' shapes alone (the latent's
    # head counts follow from them): the other set is the TPU's, traced
    # here and not lowered
    "cca_attention": Site(
        cca_k, "_kernel", _cca_operands,
        lambda *a, **s: cca_k.cca_attention(*a, **s),
        lambda *a, **s: cca_k._attend(*a, **s),
        dict(interpret=True), dict(interpret=False),
    ),
    "cca_latent": Site(
        latent_k, "_kernel", _latent_operands, _latent_through,
        lambda *a, **s: latent_k._latent(*a, heads=4, kv_heads=2, **s),
        dict(interpret=True), dict(interpret=False),
    ),
    "segment_attention": Site(
        seg_k, "_kernel", _segment_operands,
        lambda qkv, seg, heads: seg_k.segment_attention(qkv, seg, heads, interpret=True),
        lambda qkv, seg, heads: seg_k._attend(qkv, seg, heads=heads, interpret=True),
        dict(heads=2), dict(heads=4),
    ),
    "mla_segment_attention": Site(
        mla_k, "_kernel", _mla_operands,
        lambda *a, **s: mla_k.mla_segment_attention(*a, interpret=True, **s),
        lambda *a, **s: mla_k._attend(*a, interpret=True, **s),
        dict(sm_scale=0.13), dict(sm_scale=0.26),
    ),
    "eva_attention": Site(
        eva_k, "_kernel", _eva_operands,
        lambda *a, **s: eva_k.eva_attention(
            *a, 4, window=32, head_block=2, interpret=True, **EVA_TILES, **s),
        lambda *a, **s: eva_k._attend(
            *a, heads=4, window=32, head_block=2, interpret=True, **EVA_TILES, **s),
        dict(sub_tile=16), dict(sub_tile=8),
    ),
    # what `pool_chunks` is built from is its operands' shapes alone: the
    # other set is the TPU's, traced here and not lowered
    "eva_pool_chunks": Site(
        eva_k, "_pool_kernel", _pool_operands,
        lambda *a, **s: eva_k.pool_chunks(*a, **s),
        lambda *a, **s: eva_k._pool(*a, **s),
        dict(interpret=True), dict(interpret=False),
    ),
    "eva_rope": Site(
        eva_k, "_rope_kernel", lambda: _rope_operands(4 * 128),
        lambda *a, **s: eva_k.rope(*a, interpret=True, **s),
        lambda *a, **s: eva_k._rope(*a, interpret=True, **s),
        dict(scale=0.25), dict(scale=1.0),
    ),
    "hybrid_attention_global": Site(
        hybrid_k, "_kernel", _hybrid_operands, _hybrid_through, _hybrid_bare,
        dict(window=None), dict(window=48), "hybrid_attention_window",
    ),
    "hybrid_rope": Site(
        hybrid_k, "_rope_kernel", lambda: _rope_operands(4 * 64),
        lambda *a, **s: hybrid_k.rope(*a, interpret=True, **s),
        lambda *a, **s: hybrid_k._rope(*a, interpret=True, **s),
        dict(scale=0.5), dict(scale=1.0),
    ),
}


def _program(call: Callable, statics: dict) -> Callable:
    """LAYERS calls of the kernel in a Python loop, each on its own first
    operand, as a trunk's layers call it."""
    def program(first, *rest):
        outs = [call(first * (1 + i), *rest, **statics) for i in range(LAYERS)]
        return jax.tree.map(lambda *leaves: sum(leaves), *outs)

    return program


def _traces_and_lowerings() -> dict:
    trace, lower = (compile_cache.COLUMN[name] for name in ("compile.trace", "compile.lower"))
    return {
        program: (row[trace], row[lower])
        for program, row in compile_cache._RECORD.programs().items()
    }


@pytest.mark.parametrize("name", list(SITES))
def test_a_kernel_is_traced_and_lowered_once_a_program_not_once_a_layer(name, monkeypatch):
    site = SITES[name]
    operands = site.operands()
    entered = []
    body = getattr(site.module, site.body)
    monkeypatch.setattr(
        site.module, site.body, lambda *refs, **kw: entered.append(1) or body(*refs, **kw)
    )
    kernels._jitted.cache_clear()  # an earlier test's traces are not this program's
    compile_cache.reset_compiles()
    assert compile_cache.observe()

    program = _program(site.through, site.statics)
    text = jax.jit(program).trace(*operands).lower().as_text()
    # LAYERS calls: the body entered once, one `pallas_call` wrapper traced,
    # one function of the kernel's name lowered.  jax reports a jitted
    # function's every call inside a trace, the cached ones in no time, so
    # the kernel's own row counts its calls: the wrapper's counts the traces
    assert len(entered) == 1
    rows = _traces_and_lowerings()
    assert rows["wrapped"] == (1, 0)  # lowered inside the program's module, not by itself
    assert rows[name] == (LAYERS, 0)
    assert text.count(f"func.func private @{name}(") == 1
    assert text.count(f"call @{name}(") == LAYERS
    assert kernels._jitted.cache_info().currsize == 1

    # another set of statics is another function, traced once more by itself
    other_name = site.other_name or name
    traced = jax.jit(_program(site.through, site.other)).trace(*operands)
    assert len(entered) == 2
    assert kernels._jitted.cache_info().currsize == 2
    rows = _traces_and_lowerings()
    assert rows["wrapped"][0] == 2
    assert rows[other_name][0] == (1 if site.other_name else 2) * LAYERS
    if site.other.get("interpret", True):
        assert traced.lower().as_text().count(f"call @{other_name}(") == LAYERS

    # the bare call is entered a layer, and computes the same bits
    del entered[:]
    want = jax.jit(_program(site.bare, site.statics))(*operands)
    assert len(entered) == LAYERS
    got = jax.jit(program)(*operands)
    assert len(entered) == LAYERS  # the kernel's trace served this second program too
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
