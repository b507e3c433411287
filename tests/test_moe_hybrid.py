"""The hybrid-attention MoE trunk (`models/moe_hybrid.py`, one
expert-parallel rank: window layers beside global grouped-query ones, a
bias-corrected router) against its plain reference
(`chipbench/architectures/moe_hybrid_decoder/reference.py`, which imports
nothing of the program), its kernel against the dense definition, the
router's selection bias in the shared `experts.route`, the rank's share of
an expert layer, its counters and its slab shapes: at tiny sizes on the
CPU, seeded."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models import eva, moe_hybrid, moe_mla, trunk
from pathway_tpu.models import experts as moe
from pathway_tpu.models.tokenizer import (
    PACK_MAX_SEGMENTS, HashTokenizer, encode_batch, pack_batch,
)
from pathway_tpu.models.trunk import packed_positions
from pathway_tpu.ops.kernels import hybrid_attention as kernel

WINDOW = 16


def tiny_model(**changes) -> dict:
    """A configuration's `model` group at toy widths (the head's three
    widths stay the published ones: the kernel's tiling is written for
    them), under the keys the architecture's three files read: a global
    dense layer, then window, global and window expert layers."""
    model = {
        "name": "tiny-moe-hybrid", "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 1, "swa_num_key_value_heads": 2, "head_dim": 192,
        "rotary_dim": 64, "v_head_dim": 128, "sliding_window": WINDOW,
        "rope_theta": 10000000, "swa_rope_theta": 10000,
        "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False,
        "attention_value_scale": 0.707, "attention_projection_layout": "fused_qkv",
        "hybrid_layer_pattern": [0, 1, 0, 1], "moe_layer_freq": [0, 1, 1, 1],
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "n_routed_experts": 16, "num_experts_per_tok": 4, "n_shared_experts": None,
        "norm_topk_prob": True, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "n_group": 1, "topk_group": 1, "routed_scaling_factor": None,
        "hidden_act": "silu", "layernorm_epsilon": 1e-5, "vocab_size": 4096,
        "num_hidden_layers": 48, "layers": 4, "experts_held": 4, "expert_offset": 0,
        "vocab_held": 512, "ep_size": 4, "max_len": 256, "pooling": "mean",
        "dtype": "float32", "param_dtype": "float32", "bias_std": 0.02,
        "sink_mean": 4.0,
    }
    model.update(changes)
    return model


STORE = {"max_len": 256}


def text_of(words: int, seed: int) -> str:
    """A text of exactly `words` words: with [CLS] and [SEP], words + 2 tokens."""
    rng = np.random.default_rng([words, seed])
    return " ".join(f"w{int(x)}" for x in rng.integers(0, 5000, size=words))


def program_encoder(model: dict, seed: int):
    from chipbench.architectures.moe_hybrid_decoder import program
    from pathway_tpu.models import minilm

    minilm._model_cache.clear()
    return program.embedder(model, STORE, seed).encoder


def reference_vectors(model: dict, seed: int, texts: list, **kwargs) -> np.ndarray:
    from chipbench.architectures.moe_hybrid_decoder.reference import Encoder

    return Encoder(model, seed, max_len=STORE["max_len"]).embed(texts, **kwargs)


# documents of one window and a bit (19 tokens), of several (45, 70) and of
# eight (130); packed they share a row, and all but the longest start off
# the kernel's blocks and off the window's multiples
TEXTS = [text_of(17, 0), text_of(43, 1), text_of(68, 2), text_of(128, 3)]

# float32 program against the float32 reference at `highest`: what
# separates them is the order of the sums (the program's online softmax,
# packed rows and grouped matmuls), a few ulps of 1e-7 through four layers:
# 2e-5 on a unit vector's components leaves a factor of ten
F32_TOL = 2e-5


@pytest.mark.parametrize("use_flash", [False, True], ids=["dense", "kernel-interpreted"])
def test_the_packed_program_agrees_with_the_plain_reference(use_flash):
    model = tiny_model()
    enc = program_encoder(model, seed=7)
    ids, seg, slots = pack_batch(enc.tokenizer, TEXTS, max_len=256, token_budget=128)
    assert ids.shape == (1, 384)  # one row, longest first: slots 0, 130, 200 and 245
    starts = {int(np.flatnonzero(seg[r] == s + 1)[0]) for r, s in slots}
    assert any(s % WINDOW for s in starts) and any(s % 128 for s in starts)
    pooled = moe_hybrid.forward(
        enc.lm.params, enc.config, jnp.asarray(ids, jnp.int32), None,
        seg=jnp.asarray(seg, jnp.int32), max_segments=PACK_MAX_SEGMENTS,
        use_flash=use_flash,
    )
    got = np.stack([np.asarray(pooled)[r, s] for r, s in slots])
    want = reference_vectors(model, 7, TEXTS)
    np.testing.assert_allclose(got, want, atol=F32_TOL)
    np.testing.assert_allclose(np.linalg.norm(got, axis=1), 1.0, atol=1e-5)


def test_the_unpacked_form_is_the_packed_one_and_row_groups_change_nothing(monkeypatch):
    """`encode` (one text a row, the read-back's path) against the
    reference, once whole and once as four groups of two rows."""
    model = tiny_model()
    enc = program_encoder(model, seed=11)
    want = reference_vectors(model, 11, TEXTS)
    np.testing.assert_allclose(enc.encode(TEXTS), want, atol=F32_TOL)
    ids, mask = encode_batch(enc.tokenizer, TEXTS, max_len=256)
    assert ids.shape == (8, 256)
    monkeypatch.setattr(trunk, "row_chunks", lambda rows, length, cap: 4)
    grouped, stats = moe_hybrid.forward(
        enc.lm.params, enc.config, jnp.asarray(ids, jnp.int32),
        jnp.asarray(mask, jnp.int32), with_stats=True,
    )
    np.testing.assert_allclose(np.asarray(grouped)[:4], want, atol=F32_TOL)
    # the groups' statistics are summed: every real token's pairs, once
    assert int(stats["tokens"]) == 19 + 45 + 70 + 130
    assert stats["expert_tokens"].shape == (3, 4) and int(stats["overflow"].sum()) == 0


@pytest.mark.parametrize("what", ["selection bias", "sinks", "value scale", "window"])
def test_leaving_a_part_of_the_layer_out_fails_the_comparison(what):
    """The selection bias and the sinks are drawn non-zero so that a
    program without them is another model: each part left out of the
    program moves the vectors by a hundred tolerances or more."""
    model = tiny_model()
    enc = program_encoder(model, seed=7)
    params, config = enc.lm.params, enc.config
    if what == "selection bias":
        params = dict(params, layers=[
            {k: v for k, v in layer.items() if k != "router_bias"} for layer in params["layers"]
        ])
    elif what == "sinks":
        params = dict(params, layers=[
            {k: v for k, v in layer.items() if k != "sink"} for layer in params["layers"]
        ])
    elif what == "value scale":
        config = type(config)(**dict(config.__dict__, value_scale=1.0))
    else:
        config = type(config)(**dict(config.__dict__, layer_pattern=(0, 0, 0, 0)))
        params = dict(params, layers=[  # the global kind's one key head, from the window's two
            dict(layer, wk_nope=layer["wk_nope"][:, :128], wk_rope=layer["wk_rope"][:, :64],
                 wv=layer["wv"][:, :128]) if "sink" in layer else layer
            for layer in params["layers"]
        ])
    ids, mask = encode_batch(enc.tokenizer, TEXTS, max_len=256)
    got = moe_hybrid.forward(params, config, jnp.asarray(ids, jnp.int32),
                             jnp.asarray(mask, jnp.int32))
    want = reference_vectors(model, 7, TEXTS)
    assert np.abs(np.asarray(got)[:4] - want).max() > 100 * F32_TOL


def _operands(b, l, heads, kv_heads, dtype, seed=0):
    rng = np.random.default_rng(seed)
    make = lambda n, scale=1.0: jnp.asarray(  # noqa: E731
        rng.normal(size=(b, l, n)) * scale, dtype
    )
    return (make(heads * 128, 192 ** -0.5), make(heads * 64, 192 ** -0.5),
            make(kv_heads * 128), make(kv_heads * 64), make(kv_heads * 128))


def _packed_seg(l: int, docs: list) -> jnp.ndarray:
    seg = np.zeros((len(docs), l), np.int32)
    for r, lengths in enumerate(docs):
        at = 0
        for i, n in enumerate(lengths):
            seg[r, at:at + n] = i + 1
            at += n
    return jnp.asarray(seg)


_KERNEL_CASES = {
    # (heads, kv_heads, window, sink, block, dtype, tolerance)
    "global-16-a-group": (16, 1, None, False, 128, "float32", 5e-6),
    "global-with-a-sink": (4, 2, None, True, 64, "float32", 5e-6),
    "window-8-a-group-sink": (8, 1, 128, True, None, "float32", 5e-6),
    "window-narrow-no-sink": (4, 2, 48, False, 32, "float32", 5e-6),
    # bf16 operands: the kernel rounds p to bf16 a key block at a time, the
    # definition once a row; 2^-8 of values of order 1
    "global-bf16": (16, 1, None, False, 128, "bfloat16", 2e-2),
    "window-bf16-sink": (8, 1, 128, True, None, "bfloat16", 2e-2),
}


@pytest.mark.parametrize("case", sorted(_KERNEL_CASES))
def test_the_kernel_is_its_dense_definition(case):
    """Interpreted on the CPU, both kinds, with and without a sink: rows of
    several blocks, documents that begin off the blocks and a row's padded
    tail."""
    heads, kv_heads, window, sink, block, dtype, tol = _KERNEL_CASES[case]
    l = 384
    seg = _packed_seg(l, [[77, 300], [200, 100]])
    ops = _operands(2, l, heads, kv_heads, jnp.dtype(dtype))
    sinks = jnp.asarray(np.random.default_rng(1).normal(size=heads), jnp.float32) if sink else None
    pos = packed_positions(seg)
    rows = kernel.block_rows(l, window) if block is None else block
    lo = kernel.key_lo(seg, pos, rows) if window is None else None
    want = kernel.hybrid_attention_dense(*ops, seg, kv_heads=kv_heads, window=window, sink=sinks)
    got = kernel.hybrid_attention(*ops, seg, lo, kv_heads=kv_heads, window=window,
                                  sink=sinks, block=block, interpret=True)
    real = np.asarray(seg) > 0
    diff = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert diff[real].max() < tol
    assert np.isfinite(np.asarray(got, np.float32)).all()  # the padded tail too


def test_the_kernels_blocks_follow_the_documents_and_the_window():
    """`key_lo`: a block of queries of a global layer begins at its earliest
    document's first block; padding meets itself.  A window layer's block
    of queries takes the blocks its window reaches, whatever the
    documents: window 128 over blocks of 128, the block before."""
    seg = _packed_seg(1024, [[300, 500]])
    pos = packed_positions(seg)
    assert np.asarray(kernel.key_lo(seg, pos, 128)).tolist() == [[0, 0, 0, 2, 2, 2, 2, 7]]
    assert kernel.window_tiling(1024, 128) == (128, 8, 2)
    assert kernel.block_rows(24576, None) == 1024 and kernel.block_rows(24576, 128) == 128
    assert kernel.supports(24576, 64, 4, 128, 64, 128) and kernel.supports(16384, 64, 8, 128, 64, 128, 128)
    assert not kernel.supports(24576, 64, 4, 64, 64, 64)  # other heads: the dense definition
    assert not kernel.supports(24000, 64, 4, 128, 64, 128)  # a row off the blocks
    # a window whose views pass `WINDOW_KEYS`: the dense definition runs it
    assert kernel.supports(23552, 64, 8, 128, 0, 128, 897)  # 8 views of 128: [1024, 1024]
    assert not kernel.supports(23552, 64, 8, 128, 0, 128, 898)  # 9 views
    assert not kernel.supports(24576, 64, 8, 128, 64, 128, 1024)


@pytest.mark.parametrize("length,window,block,want", [
    (23552, 512, None, (128, 184, 5)),  # Laguna's: 1,472 steps a layer of 64 heads, not 7,360
    (24576, 128, None, (128, 192, 2)),  # MiMo's: the block before and its own
    (384, 48, 32, (32, 12, 3)),
    (384, 512, None, (128, 3, 3)),  # no more views than the row has blocks
], ids=["laguna", "mimo", "narrow", "short-row"])
def test_a_window_step_takes_every_key_block_its_window_reaches(length, window, block, want):
    """`window_tiling`: (rows of a block, blocks of queries, key views a
    step), one step a block of queries."""
    assert kernel.window_tiling(length, window, block) == want


@pytest.mark.parametrize("fused", [False, True], ids=["dense", "kernel-interpreted"])
def test_a_global_layer_sees_key_0_from_query_500_and_a_window_layer_does_not(fused):
    l, heads, kv_heads = 512, 4, 2
    seg = _packed_seg(l, [[512]])
    pos = packed_positions(seg)
    qn, qr, kn, kr, v = _operands(1, l, heads, kv_heads, jnp.float32)
    moved = v.at[0, 0, :].add(1.0)  # the value of key 0

    def context(values, window):
        if not fused:
            return np.asarray(kernel.hybrid_attention_dense(
                qn, qr, kn, kr, values, seg, kv_heads=kv_heads, window=window))
        lo = kernel.key_lo(seg, pos, kernel.block_rows(l, None)) if window is None else None
        return np.asarray(kernel.hybrid_attention(
            qn, qr, kn, kr, values, seg, lo, kv_heads=kv_heads, window=window, interpret=True))

    assert np.abs(context(moved, None) - context(v, None))[0, 500].max() > 1e-4
    seen = np.abs(context(moved, 128) - context(v, 128))[0]
    assert seen[500].max() == 0.0 and seen[128].max() == 0.0  # 128 - 0 is no less than the window
    assert seen[127].max() > 1e-4  # the last query that still sees it


@pytest.mark.parametrize("heads,kv_heads", [(32, 2), (16, 2)], ids=["16-a-group", "8-a-group"])
def test_a_group_of_query_heads_reads_its_own_key_value_head(heads, kv_heads):
    """Moving key/value head 1 moves the context of its query heads and of
    no other, in the kernel as in the definition."""
    l, group = 128, heads // kv_heads
    seg = _packed_seg(l, [[100]])
    pos = packed_positions(seg)
    qn, qr, kn, kr, v = _operands(1, l, heads, kv_heads, jnp.float32)
    kn2 = kn.at[:, :, 128:].multiply(-1.0)
    kr2 = kr.at[:, :, 64:].multiply(-1.0)
    v2 = v.at[:, :, 128:].add(1.0)
    lo = kernel.key_lo(seg, pos, 128)
    run = lambda *kv: np.asarray(kernel.hybrid_attention(  # noqa: E731
        qn, qr, *kv, seg, lo, kv_heads=kv_heads, interpret=True))[0, :100]
    dense = np.asarray(kernel.hybrid_attention_dense(
        qn, qr, kn2, kr2, v2, seg, kv_heads=kv_heads))[0, :100]
    before, after = run(kn, kr, v), run(kn2, kr2, v2)
    np.testing.assert_allclose(after, dense, atol=5e-6)
    by_head = np.abs(after - before).reshape(100, heads, 128).max(axis=(0, 2))
    assert (by_head[:group] == 0).all() and (by_head[group:] > 1e-3).all()


def test_the_rope_kernel_turns_the_first_64_dims_as_the_reference_does():
    """`rope` (interpreted) is `rotate`, and `rotate` is the pair (x[i],
    x[i + 32]) turned by position x theta^(-i/32)."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 256, 4 * 64)), jnp.float32)
    pos = jnp.asarray(np.stack([np.arange(256), np.r_[np.arange(100), np.arange(156)]]), jnp.int32)
    tables = kernel.rope_tables(pos, 10000.0)
    want = kernel.rotate(x, *tables, scale=0.5)
    np.testing.assert_allclose(kernel.rope(x, *tables, scale=0.5, interpret=True), want, atol=1e-6)
    angle = np.asarray(pos)[:, :, None] * 10000.0 ** (-np.arange(32) / 32.0)
    parts = np.asarray(x).reshape(2, 256, 4, 64)
    a, b = parts[..., :32], parts[..., 32:]
    cos, sin = np.cos(angle)[:, :, None], np.sin(angle)[:, :, None]
    plain = np.concatenate([a * cos - b * sin, a * sin + b * cos], -1).reshape(2, 256, 256)
    np.testing.assert_allclose(np.asarray(want), 0.5 * plain, atol=2e-5)


def test_the_bias_moves_the_selection_and_never_a_weight():
    """`experts.route`, the shared router: without a bias today's plain
    top-k to the bit; with one, other experts for some tokens, and every
    weight still the chosen score over the chosen scores' sum."""
    config = moe_hybrid.TINY
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(200, config.hidden)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(config.hidden, 16)) / 8.0, jnp.float32)
    bias = jnp.asarray(rng.normal(size=16) * 0.05, jnp.float32)
    scores = jax.nn.sigmoid(jnp.dot(h, router, preferred_element_type=jnp.float32))
    top, plain = jax.lax.top_k(scores, config.experts_per_token)  # the router as it was
    experts, weights = moe.route(h, router, config)
    assert (np.asarray(experts) == np.asarray(plain)).all()
    assert (np.asarray(weights) == np.asarray(top / top.sum(-1, keepdims=True))).all()
    chosen, w = moe.route(h, router, config, bias)
    chosen, w = np.asarray(chosen), np.asarray(w)
    moved = (np.sort(chosen, 1) != np.sort(np.asarray(plain), 1)).any(1)
    assert 20 < moved.sum() < 200  # the bias chooses otherwise for some tokens, not all
    want = np.sort(np.argsort(-(np.asarray(scores) + np.asarray(bias)), axis=1)[:, :4], 1)
    assert (np.sort(chosen, 1) == want).all()
    picked = np.take_along_axis(np.asarray(scores), chosen, axis=1)
    np.testing.assert_allclose(w, picked / picked.sum(1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(w.sum(1), 1.0, rtol=1e-6)  # routed_scaling_factor null = 1
    # A.X-K1's configuration routes through the same function, unbiased
    assert "router_bias" not in moe_mla.init_params(jax.random.PRNGKey(0), moe_mla.TINY)["layers"][1]


def test_four_ranks_add_up_to_the_uncut_layer():
    """The share test: one expert layer's routed parts on every rank of the
    deployment, with what all ranks compute alike (residual, attention)
    counted once, add up to the uncut reference's layer."""
    from chipbench.architectures.moe_hybrid_decoder import reference as R
    from chipbench.reference import weight_seed

    ranks, held, seed, n = 4, 4, 21, 70
    model = tiny_model(layers=2)
    shape = R._shape_keys(model)
    fns = R._functions(json.dumps(shape, sort_keys=True), None)
    rng = np.random.default_rng(seed)
    ids = np.zeros(128, np.int32)
    ids[:n] = rng.integers(4, model["vocab_held"], size=n)
    x = R.make_embedding(shape, seed)[jnp.asarray(ids)].astype(jnp.float32)
    x = R.run_layer(fns, shape, 0, x, n, fns["prepare"](R.make_layer(shape, seed, 0)))
    uncut_layer = fns["prepare"](R.make_layer(shape, seed, 1, experts=range(ranks * held)))
    common = np.asarray(fns["attention"](x, n, uncut_layer, window=True))
    uncut = np.asarray(R.run_layer(fns, shape, 1, x, n, uncut_layer))

    total, pairs = np.array(common), 0
    seg = jnp.asarray((np.arange(128) < n).astype(np.int32))[None]
    for rank in range(ranks):
        config = moe_hybrid.MoeHybridConfig(**dict(
            moe_hybrid.TINY.__dict__, layers=2, layer_pattern=(0, 1),
            experts_held=held, expert_offset=rank * held,
        ))
        params = moe_hybrid.init_params(jax.random.PRNGKey(weight_seed(seed)), config)
        layer = params["layers"][1]
        pos = packed_positions(seg)
        alike = jnp.asarray(np.asarray(x))[None] + moe_hybrid._attention(
            jnp.asarray(np.asarray(x))[None], layer, config, True, seg,
            kernel.rope_tables(pos, config.rope_theta_window), None, False,
        )
        # every rank computes it alike, and as the reference does
        np.testing.assert_allclose(np.asarray(alike)[0, :n], common[:n], atol=F32_TOL)
        h = trunk.rms_norm(alike[0], layer["ln2"], config.norm_eps)
        routed, counts, over = moe.held_experts(h, seg[0] > 0, layer, config)
        assert int(over) == 0
        total += np.asarray(routed)
        pairs += int(counts.sum())
    assert pairs == n * model["num_experts_per_tok"]  # every pair on one rank
    np.testing.assert_allclose(total[:n], uncut[:n], atol=5e-5)
    assert np.abs(uncut[:n] - common[:n]).max() > 0.1  # the experts add something


def test_the_counters_count_what_the_masks_let_through():
    """`hybrid.*` of a packed batch, from the segment lengths on the host,
    against the definition's own masks; the routing statistics through the
    shared path (`moe.*`); the model found by `model_module`."""
    from pathway_tpu.internals import tracing
    from pathway_tpu.models.trunk import TransformerLM, model_module

    enc = program_encoder(tiny_model(), seed=5)
    assert model_module(enc.config) is moe_hybrid and isinstance(enc.lm, TransformerLM)
    ids, seg, _ = pack_batch(enc.tokenizer, TEXTS, max_len=256, token_budget=128)
    before = tracing.spans_status()["totals"]
    enc.lm.encode_packed(ids, seg, PACK_MAX_SEGMENTS)
    enc.lm.count_stats()
    after = tracing.spans_status()["totals"]
    count = lambda name: after[name]["count"] - before.get(name, {"count": 0})["count"]  # noqa: E731
    s = np.asarray(seg, np.int32)
    at = np.arange(s.shape[1])
    see = (s[:, :, None] == s[:, None, :]) & (at[None, None, :] <= at[None, :, None]) & (s > 0)[:, :, None]
    near = at[None, :, None] - at[None, None, :] < WINDOW
    heads = enc.config.heads
    assert count("hybrid.tokens") == 19 + 45 + 70 + 130
    assert count("hybrid.global_pairs") == see.sum() * heads * 2  # layers 0 and 2
    assert count("hybrid.window_pairs") == (see & near).sum() * heads * 2  # layers 1 and 3
    assert count("hybrid.scored_pairs") == count("hybrid.global_pairs") + count("hybrid.window_pairs")
    assert count("hybrid.docs_over_window") == 4
    assert count("moe.pairs_routed") == 264 * 4 * 3 and 0 < count("moe.pairs_held") < 264 * 4 * 3
    assert count("moe.overflow_pairs") == 0
    assert count("moe.fused_returns") == 0  # 4 of 16 held: the list's return
    # the flops the utilisation gauge takes for a document are those pairs' too
    from chipbench.architectures.moe_hybrid_decoder import costs

    model = tiny_model()
    assert enc.config.active_flops_per_token(130.0) * 130 == pytest.approx(costs.flops(model, 130))
    assert costs.scored_pairs(model, 130, True) == moe_hybrid.scored_pairs(130, WINDOW) == 136 + 114 * 16
    assert costs.scored_pairs(model, 130, False) == moe_hybrid.scored_pairs(130, None) == 130 * 131 // 2


def test_served_path_ingests_and_retrieves_with_the_hybrid_embedder():
    """FusedEmbedSearch with this configuration: packed ingest, the fused
    search's unpacked queries, and the attention path counted."""
    from pathway_tpu.internals import tracing
    from pathway_tpu.ops.knn import DeviceKnnIndex, FusedEmbedSearch

    tracing.reset_spans()
    enc = program_encoder(tiny_model(), 4)
    index = DeviceKnnIndex(enc.dimension, metric="cos", reserved_space=64)
    fused = FusedEmbedSearch(enc, index)
    docs = [text_of(20 + 7 * i, i) for i in range(10)]
    payload, meta = fused.prepare_batch(list(range(10)), docs)
    assert payload[0] == "packed"
    fused.dispatch_batch(payload)
    got = fused.search_texts(docs[:3], 2)
    assert [rows[0][0] for rows in got] == [0, 1, 2]
    assert all(abs(rows[0][1] - 1.0) < 1e-4 for rows in got)
    totals = tracing.spans_status()["totals"]
    assert totals["launch.encode.attn_dense"]["count"] == 1  # off the TPU
    assert totals["hybrid.tokens"]["count"] == meta["real_tokens"]
    assert totals["moe.pairs_routed"]["count"] > 0


@pytest.mark.parametrize("n,want", [(1, 128), (14, 128), (129, 256), (1024, 1024), (1025, 2048),
                                    (8502, 9216), (16002, 16384), (24504, 24576)])
def test_a_rows_length_comes_in_the_kernels_blocks(n, want):
    shapes = moe_hybrid.tokenizer(moe_hybrid.MoeHybridConfig()).shapes
    assert shapes.seq_bucket(n) == want
    assert shapes.seq_bucket(n, maximum=16384) == min(want, 16384)


def test_the_cells_two_documents_land_in_one_row_of_24576_slots():
    """The module's `SlabShapes`: documents of 8,500 and 16,000 words are
    one row with 0.3% padding, whatever order they come in and however
    their lengths jitter; the tokenizer is plainly a `HashTokenizer`, so
    the batch is read natively (`tokenize_batch` asks the type)."""
    tok = moe_hybrid.tokenizer(moe_hybrid.MoeHybridConfig())
    assert type(tok) is HashTokenizer and tok.vocab_size == 19072
    assert HashTokenizer().shapes == type(tok.shapes)()  # the encoders' stay the defaults
    docs = [text_of(8500, 0), text_of(16000, 1)]
    shapes = set()
    for batch in (docs, docs[::-1], [text_of(8460, 2), text_of(15990, 3)]):
        ids, seg, slots = pack_batch(tok, batch, max_len=16384, token_budget=256)
        shapes.add(ids.shape)
        assert sorted(slots) == [(0, 0), (0, 1)]
        assert 1 - (seg > 0).mean() < 0.1
    assert shapes == {(1, 24576)}
    assert tok.shapes.slab_length([8502, 16002], 256, 16384) == moe_hybrid.ROW_TOKENS == 24576
    assert trunk.row_chunks(1, 24576, moe_hybrid.ROW_TOKENS) == 1
    assert trunk.row_chunks(8, 16384, moe_hybrid.ROW_TOKENS) == 8  # a read-back round: a row a group
    assert trunk.row_chunks(8, 128, moe_hybrid.ROW_TOKENS) == 1
    assert trunk.row_chunks(56, 504) == 2  # A.X-K1's cap is as it was
    # a read-back round: four documents and four probes, one row each
    ids, mask = encode_batch(tok, [docs[1]] * 4 + ["a probe of a few words"] * 4, max_len=16384)
    assert ids.shape == (8, 16384)


_TRUNKS = {
    "moe_mla": (moe_mla, "moe_mla runs one expert-parallel rank on one chip: the exchange across ranks"),
    "eva": (eva, "eva runs one pipeline stage on one chip: the hand-over between stages"),
    "moe_hybrid": (moe_hybrid, "moe_hybrid runs one expert-parallel rank on one chip: the exchange"),
}


@pytest.mark.parametrize("trunk", sorted(_TRUNKS))
def test_a_mesh_is_refused(trunk):
    """The one refusal (`trunk.one_chip_only`) names the module and
    what lives elsewhere, at every way in: `forward`, the sharding rules
    and the packed encode."""
    module, message = _TRUNKS[trunk]
    ids = jnp.zeros((1, 128), jnp.int32)
    with pytest.raises(NotImplementedError, match=message):
        module.forward({}, module.TINY, ids, jnp.ones((1, 128), jnp.int32), mesh=object())
    with pytest.raises(NotImplementedError, match="a mesh, is not built"):
        module.param_sharding_rules(module.TINY, object())
    lm = module.LM.__new__(module.LM)
    lm.config = module.TINY
    with pytest.raises(NotImplementedError, match=message):
        lm.encode_packed(ids, ids, 1, mesh=object())


# -- the kernels compile for the chip at the published widths (no chip needed) --


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back from the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kind", ["global", "window", "rope"])
def test_the_kernel_compiles_for_the_chip_at_the_ingest_slab(kind, one_chip, no_compile_cache):
    """One row of 24,576 slots, 64 query heads, bf16: what interpret mode
    cannot refuse (tiling, VMEM) the chip's compiler does, here."""
    l, heads = 24576, 64
    shape = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
    if kind == "rope":
        fn = lambda x, c, s: kernel.rope(x, c, s, scale=0.07, interpret=False)  # noqa: E731
        args = (shape(1, l, heads * 64), shape(1, l, 128, dt=jnp.float32),
                shape(1, l, 128, dt=jnp.float32))
    else:
        kv_heads, window = (4, None) if kind == "global" else (8, 128)
        fn = lambda qn, qr, kn, kr, v, seg, lo, sink: kernel.hybrid_attention(  # noqa: E731
            qn, qr, kn, kr, v, seg, lo, kv_heads=kv_heads, window=window,
            sink=sink if window else None, interpret=False)
        args = (shape(1, l, heads * 128), shape(1, l, heads * 64), shape(1, l, kv_heads * 128),
                shape(1, l, kv_heads * 64), shape(1, l, kv_heads * 128),
                shape(1, l, dt=jnp.int32),
                None if window else shape(1, l // kernel.block_rows(l, None), dt=jnp.int32),
                shape(heads, dt=jnp.float32))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kind", ["global", "window"])
def test_the_one_operand_layout_compiles_for_the_chip_at_its_cells_slab(kind, one_chip,
                                                                        no_compile_cache):
    """Laguna-XS.2's row of 23,552 slots (tests/test_laguna.py holds its
    numbers): 48 query heads of 128 over 8 key/value heads on a full
    layer, 64 behind a window of 512 on a sliding one, bf16."""
    l = 23552
    heads, window = (48, None) if kind == "global" else (64, 512)
    shape = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
    fn = lambda q, k, v, seg, lo: kernel.hybrid_attention(  # noqa: E731
        q, None, k, None, v, seg, lo, kv_heads=8, window=window, interpret=False)
    args = (shape(1, l, heads * 128), shape(1, l, 8 * 128), shape(1, l, 8 * 128),
            shape(1, l, dt=jnp.int32),
            None if window else shape(1, l // kernel.block_rows(l, None), dt=jnp.int32))
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("rows,length", [(56, 504), (8, 512)])
def test_the_cca_kernel_compiles_for_the_chip_at_its_cells_slabs(rows, length, one_chip,
                                                                 no_compile_cache):
    """`ops/kernels/cca_attention.py` (tests/test_zaya.py holds its numbers)
    at the ingest slab and the read-back's, 8 query over 2 key/value heads,
    bf16.  Here and not beside its other tests: one file holds the
    fixture that describes the chip, because one process may."""
    from pathway_tpu.ops.kernels import cca_attention

    shape = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
    fn = lambda q, k, v, seg: cca_attention.cca_attention(q, k, v, seg, interpret=False)  # noqa: E731
    args = (shape(rows, length, 8 * 128), shape(rows, length, 2 * 128),
            shape(rows, length, 2 * 128), shape(rows, length, dt=jnp.int32))
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("rows,length", [(56, 504), (8, 512), (8, 40)])
def test_the_cca_latent_kernel_compiles_for_the_chip_at_its_cells_slabs(rows, length, one_chip,
                                                                        no_compile_cache):
    """`ops/kernels/cca_latent.py` (tests/test_zaya.py holds its numbers) at
    the ingest slab, a full read-back slab and a short one, 8 query over 2
    key/value heads, two taps a convolution, bf16: a slab row's whole block
    and its float32 working set fit the VMEM the call asks for."""
    from pathway_tpu.ops.kernels import cca_latent

    shape = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
    layer = {"conv0_w": shape(2, 1280), "conv0_b": shape(1280),
             "conv1_w": shape(10, 256, 128, dt=jnp.bfloat16), "conv1_b": shape(1280),
             "tau": shape(2)}
    fn = lambda qkv, seg, cos, sin, layer: cca_latent.cca_latent(  # noqa: E731
        qkv, seg, (cos, sin), layer, heads=8, kv_heads=2, interpret=False)
    args = (shape(rows, length, 12 * 128, dt=jnp.bfloat16), shape(rows, length, dt=jnp.int32),
            shape(rows, length, 128), shape(rows, length, 128), layer)
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def test_mimo_builds_the_leaves_and_shapes_it_built():
    """The trunk learned kinds that differ in query heads, rotary width,
    ladder and gate, and a shared expert (PR 44); MiMo-V2.5's configuration
    builds the same parameters as before: the tiny one's leaves to the
    bit, the published one's names, shapes and types (digests taken at the
    parent commit, 222fe9d)."""
    import hashlib

    params = moe_hybrid.init_params(jax.random.PRNGKey(3), moe_hybrid.TINY)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    digest = hashlib.sha256()
    for path, leaf in flat:
        digest.update(jax.tree_util.keystr(path).encode())
        digest.update(np.asarray(leaf).tobytes())
    assert (len(flat), digest.hexdigest()[:16]) == (54, "031d3d8f2a0cc4dd")
    shapes = jax.eval_shape(
        lambda key: moe_hybrid.init_params(key, moe_hybrid.MoeHybridConfig()),
        jax.random.PRNGKey(0),
    )
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    digest = hashlib.sha256()
    for path, leaf in flat:
        digest.update(f"{jax.tree_util.keystr(path)}{leaf.shape}{leaf.dtype}".encode())
    assert (len(flat), digest.hexdigest()[:16]) == (96, "920637d70def1a81")
    assert not moe_hybrid.MoeHybridConfig().whole_heads
