"""The Laguna-family trunk (`models/moe_hybrid.py` configured by layer
kind: window layers of 8 query heads with all 128 dims rotated beside full
layers of 4 with YaRN on 64 of 128, a head-wise output gate, every routed
expert held at top-4 beside a shared one) against its plain reference
(`chipbench/architectures/laguna_decoder/reference.py`, which imports
nothing of the program); each mechanism left out in turn; the one-operand
layout of the attention kernel against its dense definition; the share
test of an expert layer; `held_experts` with every expert held; the
cell's configuration and counters.  At tiny sizes on the CPU, seeded,
except the head (128 wide, 64 rotated on full layers), which the kernel's
layout needs."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models import experts as moe
from pathway_tpu.models import moe_hybrid
from pathway_tpu.models.tokenizer import PACK_MAX_SEGMENTS, pack_batch
from pathway_tpu.models.trunk import packed_positions
from pathway_tpu.ops.kernels import hybrid_attention as kernel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW = 16
LAYERS = ["full_attention", "sliding_attention", "sliding_attention", "full_attention"]


def tiny_model(**changes) -> dict:
    """A configuration's `model` group at toy widths under the keys the
    architecture's three files read: a full dense layer, then sliding,
    sliding and full sparse layers.  The YaRN ladder's original length is
    cut with the positions (256 for 262,144 / 64 at the published 4,096),
    so that its ramp turns dims that rotate within a tiny text."""
    model = {
        "name": "tiny-laguna", "model_type": "laguna", "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 128, "attention_bias": False,
        "rms_norm_eps": 1e-6, "num_experts": 16, "num_experts_per_tok": 4,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "tie_word_embeddings": False, "gating": True, "sliding_window": WINDOW,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 10000, "rope_type": "yarn", "factor": 8,
                "original_max_position_embeddings": 256, "beta_slow": 1, "beta_fast": 32,
                "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5,
            },
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1,
            },
        },
        "layer_types": LAYERS, "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
        "num_attention_heads_per_layer": [4, 8, 8, 4],
        "moe_apply_router_weight_on_input": False, "moe_routed_scaling_factor": 2.5,
        "vocab_size": 4096, "layers": 4, "experts_held": 16, "expert_offset": 0,
        "vocab_held": 512, "pp_size": 8, "max_len": 256, "pooling": "mean",
        "dtype": "float32", "param_dtype": "float32", "hidden_act": "silu",
        "scoring_func": "sigmoid", "gate_form": "head-wise",
    }
    model.update(changes)
    return model


STORE = {"max_len": 256}


def text_of(words: int, seed: int) -> str:
    """A text of exactly `words` words: with [CLS] and [SEP], words + 2 tokens."""
    rng = np.random.default_rng([words, seed])
    return " ".join(f"w{int(x)}" for x in rng.integers(0, 5000, size=words))


def program_encoder(model: dict, seed: int):
    from chipbench.architectures.laguna_decoder import program
    from pathway_tpu.models import minilm

    minilm._model_cache.clear()
    return program.embedder(model, STORE, seed).encoder


def reference_vectors(model: dict, seed: int, texts: list) -> np.ndarray:
    from chipbench.architectures.laguna_decoder.reference import Encoder

    return Encoder(model, seed, max_len=STORE["max_len"]).embed(texts)


def packed_vectors(enc, texts, config=None, use_flash=False, token_budget=128):
    """The packed program's vectors of `texts` in their order, under
    `config` (default: the encoder's) with the encoder's parameters."""
    ids, seg, slots = pack_batch(enc.tokenizer, texts, max_len=256, token_budget=token_budget)
    pooled = moe_hybrid.forward(
        enc.lm.params, config or enc.config, jnp.asarray(ids, jnp.int32), None,
        seg=jnp.asarray(seg, jnp.int32), max_segments=PACK_MAX_SEGMENTS,
        use_flash=use_flash,
    )
    return np.stack([np.asarray(pooled)[r, s] for r, s in slots]), ids, seg, slots


# documents of under a window (9 tokens), of one and a bit (19), of several
# (45, 70) and of eight (130): packed two to five a row, starting off the
# window's multiples and off the kernel's blocks
TEXTS = [text_of(7, 0), text_of(17, 1), text_of(43, 2), text_of(68, 3), text_of(128, 4)]
TEXTS_AT = tuple(range(len(TEXTS)))

# float32 program against the float32 reference at `highest`: what
# separates them is the order of the sums (the online softmax, packed rows,
# the grouped matmuls), a few ulps of 1e-7 through four layers: 2e-5 on a
# unit vector's components leaves a factor of ten
F32_TOL = 2e-5


@pytest.mark.parametrize("use_flash", [False, True], ids=["dense", "kernel-interpreted"])
@pytest.mark.parametrize("docs", [(3, 4), (0, 1, 4), TEXTS_AT], ids=["two", "three", "five"])
def test_the_packed_program_agrees_with_the_plain_reference(docs, use_flash):
    """Two, three and five documents in one row, some under the window and
    some over it."""
    model = tiny_model()
    enc = program_encoder(model, seed=7)
    texts = [TEXTS[i] for i in docs]
    got, ids, seg, slots = packed_vectors(enc, texts, use_flash=use_flash)
    assert ids.shape[0] == 1 and len(slots) == len(docs)
    if len(docs) == 5:
        assert ids.shape == (1, 384)  # 271 tokens
        starts = {int(np.flatnonzero(seg[r] == s + 1)[0]) for r, s in slots}
        assert any(s % WINDOW for s in starts) and any(s % 128 for s in starts)
    want = reference_vectors(model, 7, texts)
    np.testing.assert_allclose(got, want, atol=F32_TOL)
    np.testing.assert_allclose(np.linalg.norm(got, axis=1), 1.0, atol=1e-5)


def _without(config, mechanism: str):
    """The program's configuration with one mechanism left out (or, for
    heads by kind, the window layers given the full layers' count)."""
    full = config.yarn_global
    changes = {
        "gate": dict(head_gate=False),
        "yarn_attention_factor": dict(yarn_global=full[:4] + (1.0,)),
        "yarn_ramp": dict(yarn_global=(1.0,) + full[1:]),
        "partial_rotary_full": dict(rotary_dim=config.head_dim),
        "full_rotary_window": dict(rotary_dim_window=config.rotary_dim),
        "window": dict(window=10**6),
        "shared_expert": dict(shared_mlp_dim=0),
        "routed_scale": dict(routed_scaling_factor=1.0),
        "heads_by_kind": dict(heads_window=None),
    }[mechanism]
    return dataclasses.replace(config, **changes)


@pytest.mark.parametrize("mechanism", [
    "gate", "yarn_attention_factor", "yarn_ramp", "partial_rotary_full",
    "full_rotary_window", "window", "shared_expert", "routed_scale", "heads_by_kind",
])
def test_each_mechanism_moves_the_vectors(mechanism):
    """The program with one mechanism left out against the reference with
    all of them: each is off by ten tolerances or more, so the agreement
    above holds every one of them."""
    from pathway_tpu.models.trunk import model_module

    model = tiny_model()
    enc = program_encoder(model, seed=5)
    config = _without(enc.config, mechanism)
    params = enc.lm.params
    if mechanism == "heads_by_kind":  # other shapes: the recipe's own weights
        params = model_module(config).init_params(jax.random.PRNGKey(5), config)
    gone = {"gate": ("head_gate",),
            "shared_expert": ("shared_gate", "shared_up", "shared_down")}.get(mechanism, ())
    params = dict(params, layers=[
        {name: w for name, w in layer.items() if name not in gone} for layer in params["layers"]
    ])
    ids, seg, slots = pack_batch(enc.tokenizer, TEXTS, max_len=256, token_budget=128)
    pooled = moe_hybrid.forward(
        params, config, jnp.asarray(ids, jnp.int32), None,
        seg=jnp.asarray(seg, jnp.int32), max_segments=PACK_MAX_SEGMENTS,
    )
    got = np.stack([np.asarray(pooled)[r, s] for r, s in slots])
    want = reference_vectors(model, 5, TEXTS)
    assert np.abs(got - want).max() > 10 * F32_TOL, mechanism


# documents (first slot, end) in a row of `length` slots, the rest padding
_DOCUMENTS = ((0, 300), (300, 1500), (1500, 1530), (1530, 2000))
# at a window of 512 over blocks of 128 (five views): documents that begin
# inside a block of queries' earliest view and inside its own, one of 55
# slots and one of 7 in the middle of windows, one that begins on a block
_EDGES = ((0, 645), (645, 700), (700, 1283), (1283, 1290), (1290, 1408), (1408, 1990))
_ONE_OPERAND_CASES = {
    # (window, group, length, documents)
    "global-6": (None, 6, 2048, _DOCUMENTS),
    "global-8": (None, 8, 2048, _DOCUMENTS),
    "window-512-6": (512, 6, 2048, _DOCUMENTS),
    "window-512-8": (512, 8, 2048, _DOCUMENTS),
    "window-512-8-view-edges": (512, 8, 2048, _EDGES),
    # three blocks of queries: fewer views than the window reaches
    "window-512-6-short-row": (512, 6, 384, ((0, 200), (200, 330))),
    "global-6-short-row": (None, 6, 384, ((0, 200), (200, 330))),
}


@pytest.mark.parametrize("case", list(_ONE_OPERAND_CASES))
def test_the_one_operand_kernel_agrees_with_its_dense_definition(case):
    """Interpreted on the CPU at the cell's tiling (blocks of 1,024 keys
    for a global layer; for a window of 512, one step a block of 128
    queries over five views of 128 keys), documents longer and shorter
    than the window in one row, every view's edges; the padded tail
    finite."""
    window, group, length, documents = _ONE_OPERAND_CASES[case]
    kv = 1
    rng = np.random.default_rng(group)
    seg = np.zeros((1, length), np.int32)
    for s, (lo, hi) in enumerate(documents):
        seg[0, lo:hi] = s + 1
    seg = jnp.asarray(seg)
    heads = group * kv
    q = jnp.asarray(rng.standard_normal((1, length, heads * 128)) * 0.1, jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, length, kv * 128)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, length, kv * 128)), jnp.float32)
    assert kernel.supports(length, heads, kv, 128, 0, 128, window)
    lo = None
    if window is None:
        lo = kernel.key_lo(seg, packed_positions(seg), kernel.block_rows(length, window))
    got = kernel.hybrid_attention(q, None, k, None, v, seg, lo, kv_heads=kv, window=window,
                                  interpret=True)
    want = kernel.hybrid_attention_dense(q, None, k, None, v, seg, kv_heads=kv, window=window)
    real = np.asarray(seg)[0] > 0
    np.testing.assert_allclose(np.asarray(got)[0, real], np.asarray(want)[0, real], atol=2e-6)
    assert np.isfinite(np.asarray(got)).all()
    assert kernel.op_name(False, window) == (
        "laguna_attention_global" if window is None else "laguna_attention_window"
    )


def test_the_kernel_gate_takes_the_cells_head_layouts():
    """Both kinds of the cell's row pass `supports`; MiMo's layout still
    does, and a group of 3 does for one operand and not for two (two
    heads' rope queries share a tile)."""
    assert kernel.supports(23552, 48, 8, 128, 0, 128, None)
    assert kernel.supports(23552, 64, 8, 128, 0, 128, 512)
    assert kernel.supports(24576, 64, 4, 128, 64, 128, None)
    assert kernel.supports(23552, 24, 8, 128, 0, 128, None)
    assert not kernel.supports(23552, 24, 8, 128, 64, 128, None)
    assert not kernel.supports(23552, 48, 8, 64, 64, 128, None)


def _share_config(held: int, offset: int):
    from chipbench.architectures.laguna_decoder import program

    model = tiny_model(experts_held=held, expert_offset=offset, layers=2)
    return program.config_of(model, STORE)


def test_two_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The guide's share test: with 8 of 16 experts held at offsets 0 and
    8, the two shares' routed parts, plus the shared expert (which every
    stage holds alike) counted once, are the uncut layer's."""
    whole = _share_config(16, 0)
    halves = [_share_config(8, 0), _share_config(8, 8)]
    params = [moe_hybrid.init_params(jax.random.PRNGKey(9), c)["layers"][1]
              for c in [whole] + halves]
    # a share's experts are the uncut model's (fold_in of the global index)
    for name in ("experts_gate", "experts_up", "experts_down"):
        np.testing.assert_array_equal(params[0][name][:8], params[1][name])
        np.testing.assert_array_equal(params[0][name][8:], params[2][name])
    h = jnp.asarray(np.random.default_rng(0).standard_normal((96, 64)), jnp.float32)
    valid = jnp.arange(96) < 90
    routed = [moe.held_experts(h, valid, p, c)[0] for p, c in zip(params, [whole] + halves)]
    shared = moe.swiglu(
        h, params[0]["shared_gate"], params[0]["shared_up"], params[0]["shared_down"]
    )
    np.testing.assert_allclose(routed[1] + routed[2] + shared, routed[0] + shared, atol=1e-5)
    assert float(jnp.abs(routed[1]).max()) > 1e-3 and float(jnp.abs(routed[2]).max()) > 1e-3


@pytest.mark.parametrize("tokens", [96, 5000], ids=["a-slot-a-token", "above-4096"])
def test_held_experts_with_every_expert_held_at_top8(tokens):
    """256 experts all held, 8 a token: the buffer holds every pair (no
    overflow), the return needs no list that could spill, each group begins
    a tile, and y is a plain loop over the experts.  The return is the
    fused weighted sum at the tokens' side: no loop in the program, and the
    padding's rows exact zeros."""
    c = dataclasses.replace(
        moe_hybrid.TINY, hidden=32, expert_mlp_dim=16, n_routed_experts=256,
        experts_per_token=8, experts_held=256, routed_scaling_factor=2.5,
    )
    rng = np.random.default_rng(tokens)
    layer = {
        "router": jnp.asarray(rng.standard_normal((32, 256)) / np.sqrt(32), jnp.float32),
        "experts_gate": jnp.asarray(rng.standard_normal((256, 32, 16)) / np.sqrt(32), jnp.float32),
        "experts_up": jnp.asarray(rng.standard_normal((256, 32, 16)) / np.sqrt(32), jnp.float32),
        "experts_down": jnp.asarray(rng.standard_normal((256, 16, 32)) / 4, jnp.float32),
    }
    h = jnp.asarray(rng.standard_normal((tokens, 32)), jnp.float32)
    valid = jnp.arange(tokens) < tokens - 7
    capacity = moe.pair_capacity(tokens, c)
    assert moe.combine_rows(tokens, c) == tokens
    assert moe.returns_fused(c)
    program = jax.jit(
        lambda h, valid, layer: moe.held_experts(h, valid, layer, c, with_stats=True)
    )
    assert "while" not in program.lower(h, valid, layer).as_text()
    y, counts, over, stats = program(h, valid, layer)
    assert int(stats["fused_returns"]) == 1
    assert not np.asarray(y)[tokens - 7:].any()  # padding routes nothing: exact zeros
    assert int(over) == 0 and int(stats["combine_spills"]) == 0
    assert int(counts.sum()) == 8 * (tokens - 7)
    assert int(stats["groups_aligned"]) == 1
    tiles = int((-(-counts // moe.PAIR_ROWS)).sum()) * moe.PAIR_ROWS
    assert int(stats["group_rows"]) == tiles <= capacity
    assert int(stats["group_pad_rows"]) == tiles - 8 * (tokens - 7)
    # the plain loop over the experts, every expert on every token at once
    experts, weights = moe.route(h, layer["router"], c)
    chose = (experts[:, :, None] == jnp.arange(256)) & valid[:, None, None]
    w = jnp.sum(jnp.where(chose, weights[:, :, None], 0.0), axis=1)  # [tokens, 256]
    hi = jax.lax.Precision.HIGHEST
    act = jax.nn.silu(jnp.einsum("td,edf->etf", h, layer["experts_gate"], precision=hi))
    act = act * jnp.einsum("td,edf->etf", h, layer["experts_up"], precision=hi)
    out = jnp.einsum("etf,efd->etd", act, layer["experts_down"], precision=hi)
    want = jnp.einsum("te,etd->td", w, out, precision=hi)
    np.testing.assert_allclose(y, want, atol=2e-5)


def test_the_cells_configuration_is_the_published_model():
    """The program's reading of the cell's file: every width, both kinds'
    heads, rotary widths and ladders, the window, the router and the
    experts as published, five layers in the model's order."""
    from chipbench.architectures.laguna_decoder import program

    cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                      "laguna-xs2-pp8-docstore.json")))
    c = program.config_of(cfg["model"], cfg["store"])
    assert (c.hidden, c.layers, c.layer_pattern, c.first_k_dense) == (
        2048, 5, (False, True, True, True, False), 1
    )
    assert (c.heads, c.heads_window, c.kv_heads_global, c.kv_heads_window) == (48, 64, 8, 8)
    assert (c.head_dim, c.v_head_dim, c.rotary(False), c.rotary(True)) == (128, 128, 64, 128)
    assert c.yarn(False) == (64.0, 4096, 64.0, 1.0, 1.4158883083359672) and c.yarn(True) == ()
    assert (c.rope_theta_global, c.rope_theta_window, c.window) == (500000.0, 10000.0, 512)
    assert (c.head_gate, c.sink_global, c.sink_window) == (True, False, False)
    assert (c.dense_mlp_dim, c.expert_mlp_dim, c.shared_mlp_dim) == (8192, 512, 512)
    assert (c.n_routed_experts, c.experts_per_token, c.experts_held) == (256, 8, 256)
    assert (c.routed_scaling_factor, c.selection_bias, c.depth) == (2.5, False, 40)
    assert (c.vocab_size, c.max_len, c.pp_size, c.whole_heads) == (100352, 8192, 8, True)
    # the cell's slab: one row of 23,552 slots, every pair in the buffer
    assert moe.pair_capacity(23552, c) == 319488
    assert moe.combine_rows(23552, c) == 23552
    # stage 0 of eight refuses a mesh, naming the hand-over
    with pytest.raises(NotImplementedError, match="stage 0 of 8 on one chip: the hand-over"):
        moe_hybrid.param_sharding_rules(c, object())


def test_the_counters_count_pairs_by_kind_and_the_buffers_rows():
    """`hybrid.*_pairs` multiply by each kind's query heads; the expert
    path counts the grouped matmuls' rows and those that hold no pair."""
    from pathway_tpu.internals import tracing

    model = tiny_model()
    enc = program_encoder(model, seed=2)
    texts = [text_of(30, 1), text_of(100, 2)]
    names = ("hybrid.global_pairs", "hybrid.window_pairs", "moe.group_rows",
             "moe.group_pad_rows", "moe.pairs_held", "moe.overflow_pairs",
             "moe.combine_spills", "moe.fused_returns")

    def counts():
        totals = tracing.spans_status()["totals"]
        return {n: totals.get(n, {"count": 0})["count"] for n in names}

    before = counts()
    enc.encode_packed(texts)
    enc.lm.count_stats()
    after = counts()
    got = {n: after[n] - before[n] for n in names}
    lengths = np.array([32, 102])
    full = int((lengths * (lengths + 1) // 2).sum())
    first = np.minimum(lengths, WINDOW)
    window = int((first * (first + 1) // 2 + (lengths - first) * WINDOW).sum())
    assert got["hybrid.global_pairs"] == full * 4 * 2  # 4 heads, two full layers
    assert got["hybrid.window_pairs"] == window * 8 * 2  # 8 heads, two sliding layers
    assert got["moe.pairs_held"] == 134 * 4 * 3  # every pair of three sparse layers
    assert got["moe.overflow_pairs"] == 0 and got["moe.combine_spills"] == 0
    assert got["moe.group_rows"] >= got["moe.pairs_held"]
    assert got["moe.group_pad_rows"] == got["moe.group_rows"] - got["moe.pairs_held"]
    # every expert held at top-4: each of three sparse layers returned fused,
    # in each of the batch's row groups (one: two short texts)
    assert got["moe.fused_returns"] == 3


def test_the_window_steps_count_the_pairs_their_tiling_meets(monkeypatch):
    """`hybrid.window_met_pairs` of a batch of the cell's slab, one row of
    23,552 slots: 184 blocks of queries, each five views of 128 keys for
    128 rows, in each of 64 query heads and three window layers, whatever
    the documents; over `hybrid.window_pairs`, the tiling's padding."""
    from chipbench.architectures.laguna_decoder import program
    from pathway_tpu.internals import tracing

    cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                      "laguna-xs2-pp8-docstore.json")))
    lm = moe_hybrid.LM.__new__(moe_hybrid.LM)
    lm.config = program.config_of(cfg["model"], cfg["store"])
    # the counting alone: the program itself is not run
    monkeypatch.setattr(moe_hybrid.LM, "_dispatch", lambda *a, **k: None)
    seg = np.zeros((1, 23552), np.int32)
    for s, at in enumerate(range(0, 23400, 1950)):  # 12 files of 1,950 tokens
        seg[0, at:at + 1950] = s + 1
    names = ("hybrid.window_met_pairs", "hybrid.window_pairs")

    def counts():
        totals = tracing.spans_status()["totals"]
        return {n: totals.get(n, {"count": 0})["count"] for n in names}

    before = counts()
    lm.encode_packed(np.zeros_like(seg), seg, PACK_MAX_SEGMENTS)
    got = {n: counts()[n] - before[n] for n in names}
    assert got["hybrid.window_met_pairs"] == 184 * 5 * 128 * 128 * 64 * 3
    lengths = np.full(12, 1950)
    assert got["hybrid.window_pairs"] == int(moe_hybrid.scored_pairs(lengths, 512).sum()) * 64 * 3
    assert 1.4 < got["hybrid.window_met_pairs"] / got["hybrid.window_pairs"] < 1.5
