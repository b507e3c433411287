"""The latent-attention + shared-expert MoE trunk (`models/moe_mla.py`, one
expert-parallel rank) against its plain reference
(`chipbench/architectures/moe_mla_decoder/reference.py`, which imports
nothing of the program), at tiny sizes on the CPU, seeded."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models import experts as moe
from pathway_tpu.models import trunk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_model(**changes) -> dict:
    """A configuration's `model` group at toy widths, under the keys the
    architecture's three files read (the published ones plus the cut's)."""
    model = {
        "name": "tiny-moe-mla", "hidden_size": 96, "num_attention_heads": 4,
        "q_lora_rank": 40, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 160,
        "moe_intermediate_size": 48, "first_k_dense_replace": 1,
        "n_routed_experts": 16, "num_experts_per_tok": 4, "n_shared_experts": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "topk_method": "none", "hidden_act": "silu",
        "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
                         "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096, "type": "yarn"},
        "vocab_size": 4096, "num_hidden_layers": 61,
        "layers": 3, "experts_held": 4, "expert_offset": 0, "vocab_held": 512,
        "ep_size": 4, "pooling": "mean", "dtype": "float32", "param_dtype": "float32",
    }
    model.update(changes)
    return model


STORE = {"max_len": 64}


def texts(n: int, seed: int = 0, lo: int = 5, hi: int = 40) -> list:
    rng = np.random.default_rng(seed)
    return [
        " ".join(f"w{int(x)}" for x in rng.integers(0, 5000, size=int(k)))
        for k in rng.integers(lo, hi, size=n)
    ]


def program_encoder(model: dict, seed: int):
    from chipbench.architectures.moe_mla_decoder import program
    from pathway_tpu.models import minilm

    minilm._model_cache.clear()
    return program.embedder(model, STORE, seed).encoder


def reference_encoder(model: dict, seed: int):
    from chipbench.architectures.moe_mla_decoder.reference import Encoder

    return Encoder(model, seed, max_len=STORE["max_len"])


def gap(a: np.ndarray, b: np.ndarray) -> float:
    """Widest |difference| of two sets of unit vectors' coordinates, in
    units of a coordinate's own scale (1/sqrt(d))."""
    return float(np.abs(a - b).max() * np.sqrt(a.shape[1]))


# Tolerances, in coordinate scales (`gap`), each set from readings on the
# CPU over seeds 11, 12, 13 and 2030405060.  float32: both sides compute in
# f32 and differ in summation order only: 1.3e-6 to 1.8e-6 is read, the
# tolerance is ten times that.  bfloat16 (parameters rounded to bf16 on
# both sides, the program also computes in bf16, so a token near a tie of
# the router's top-k may take another expert than the reference's):
# 0.040 to 0.135 is read, the fp8 control reads 0.40 to 0.56 and int8 0.10 to
# 0.21; 0.25 lies between the program's widest and the fp8 control's
# smallest, with room on both sides.
F32_TOL = 2e-5
BF16_TOL = 0.25


@pytest.mark.parametrize("form", ["packed", "unpacked"])
@pytest.mark.parametrize("seed", [3, 2030405060])
def test_program_matches_reference_float32(form, seed):
    model = tiny_model()
    docs = texts(12, seed)
    enc = program_encoder(model, seed)
    got = enc.encode_packed(docs) if form == "packed" else enc.encode(docs)
    want = reference_encoder(model, seed).embed(docs)
    assert got.shape == want.shape == (12, 96)
    assert gap(got, want) < F32_TOL


@pytest.mark.parametrize("form", ["packed", "unpacked"])
@pytest.mark.parametrize("seed", [11, 13])
def test_program_matches_reference_bfloat16_and_lower_precisions_fail(form, seed):
    docs = texts(12, seed)
    model = tiny_model(dtype="bfloat16", param_dtype="bfloat16")
    enc = program_encoder(model, seed)
    got = enc.encode_packed(docs) if form == "packed" else enc.encode(docs)
    ref = reference_encoder(model, seed)
    want = ref.embed(docs)
    assert gap(got, want) < BF16_TOL
    # a bf16 run in a float32 configuration's place fails the f32 tolerance
    want_f32 = reference_encoder(tiny_model(), seed).embed(docs)
    assert gap(got, want_f32) > F32_TOL
    # and the fp8 control fails the bf16 one
    assert gap(ref.embed(docs, lower_precision="fp8"), want) > BF16_TOL


def test_packed_causal_equals_each_document_alone_and_unpacked():
    """Packing changes nothing a document can see: positions restart, the
    mask is causal within the segment, routing is per token."""
    from pathway_tpu.models import moe_mla as M

    config = M.TINY
    params = M.init_params(jax.random.PRNGKey(5), config)
    rng = np.random.default_rng(5)
    l = 48
    ids = rng.integers(4, config.vocab_size, size=(2, l)).astype(np.int32)
    seg = np.zeros((2, l), np.int32)
    docs = [(0, 0, 20), (0, 20, 45), (1, 0, 40)]  # row, from, to
    for slot, (row, lo, hi) in enumerate([(0, 0, 20), (0, 20, 45)]):
        seg[row, lo:hi] = slot + 1
    seg[1, 0:40] = 1
    packed, stats = M.forward(
        params, config, ids, None, seg=jnp.asarray(seg), max_segments=4,
        with_stats=True,
    )
    assert int(stats["tokens"]) == 85 and int(stats["overflow"].sum()) == 0
    assert stats["expert_tokens"].shape == (config.expert_layers, config.experts_held)
    for (row, lo, hi), slot in zip(docs, (0, 1, 0)):
        one = np.zeros((1, l), np.int32)
        mask = np.zeros((1, l), np.int32)
        one[0, : hi - lo], mask[0, : hi - lo] = ids[row, lo:hi], 1
        alone = M.forward(params, config, jnp.asarray(one), jnp.asarray(mask))
        np.testing.assert_allclose(
            np.asarray(alone)[0], np.asarray(packed)[row, slot], atol=2e-6
        )
    # causal: a document's later tokens do not reach its earlier ones
    cut = np.array(ids)
    cut[1, 30:40] = 7
    prefix_seg = np.array(seg)
    prefix_seg[1, 30:] = 0
    a = M.forward(params, config, ids, None, seg=jnp.asarray(prefix_seg), max_segments=4)
    b = M.forward(params, config, cut, None, seg=jnp.asarray(prefix_seg), max_segments=4)
    np.testing.assert_allclose(np.asarray(a)[1, 0], np.asarray(b)[1, 0], atol=1e-7)


def test_rows_in_groups_equal_the_whole_slab(monkeypatch):
    """A slab over CHUNK_TOKENS runs as groups of rows inside one program:
    the same vectors and the same counts."""
    from pathway_tpu.models import moe_mla as M

    config = M.TINY
    params = M.init_params(jax.random.PRNGKey(6), config)
    rng = np.random.default_rng(6)
    ids = rng.integers(4, config.vocab_size, size=(6, 32)).astype(np.int32)
    seg = (rng.random((6, 32)) < 0.9).astype(np.int32)
    seg[:, 16:] *= 2
    assert trunk.row_chunks(6, 32) == 1 and trunk.row_chunks(56, 504) == 2
    whole = M.forward(params, config, ids, None, seg=jnp.asarray(seg), max_segments=2,
                      with_stats=True)
    monkeypatch.setattr(trunk, "CHUNK_TOKENS", 64)
    assert trunk.row_chunks(6, 32) == 3
    parts = M.forward(params, config, ids, None, seg=jnp.asarray(seg), max_segments=2,
                      with_stats=True)
    np.testing.assert_allclose(np.asarray(whole[0]), np.asarray(parts[0]), atol=2e-6)
    for key in ("expert_tokens", "overflow", "tokens"):
        np.testing.assert_array_equal(np.asarray(whole[1][key]), np.asarray(parts[1][key]))


def _mla_operands(b, l, heads, dtype, seed=0):
    rng = np.random.default_rng(seed)

    def draw(width):
        return jnp.asarray(rng.normal(size=(b, l, width)), dtype=jnp.dtype(dtype))

    return (draw(heads * 128), draw(heads * 64), draw(heads * 128), draw(64),
            draw(heads * 128))


# rows' (segments, tokens used) as tests/test_kernels.py lays slabs out
_MLA_SLABS = {
    # the ingest slab's length: two query blocks, the key axis padded
    # 504 -> 512; a row of many documents, one all padding, two and padding
    "L504": (504, [(9, 504), (0, 0), (2, 311)]),
    # three 128-row query blocks (384 does not divide into 256s)
    "L300": (300, [(1, 300), (3, 200)]),
    # a search bucket: one query block, the key axis padded 40 -> 128
    "L40": (40, [(1, 9), (2, 40)]),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("slab", sorted(_MLA_SLABS))
def test_mla_attention_kernel_matches_dense_definition(slab, dtype):
    """`ops/kernels/mla_attention.py`, interpreted, against the dense
    `_mla_segment_attention`, which stays its numerical definition: equal
    on every valid token, finite everywhere."""
    from pathway_tpu.models.moe_mla import _mla_segment_attention
    from pathway_tpu.ops.kernels.mla_attention import mla_segment_attention
    from tests.test_kernels import _slab_segments

    l, layouts = _MLA_SLABS[slab]
    heads, scale = 4, 0.13
    operands = _mla_operands(len(layouts), l, heads, dtype)
    seg = _slab_segments(l, layouts)
    out = mla_segment_attention(
        *operands, jnp.asarray(seg), sm_scale=scale, interpret=True
    )
    assert out.shape == (len(layouts), l, heads * 128) and out.dtype == operands[0].dtype
    ref = _mla_segment_attention(*operands, jnp.asarray(seg), scale, heads)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert np.isfinite(out).all()
    # as test_segment_attention_matches_dense_definition: f32 the flash
    # test's tolerance; bf16 two ulps of the rounded output
    tol = 2e-3 if dtype == "float32" else 2.0 ** -6
    valid = seg > 0
    np.testing.assert_allclose(out[valid], ref[valid], rtol=tol, atol=tol)


def test_mla_attention_kernel_shapes_and_choice(monkeypatch):
    from pathway_tpu.models import moe_mla as M
    from pathway_tpu.ops.kernels import mla_attention as K

    assert K.supports(504, 64, 128, 64, 128)
    assert not K.supports(520, 64, 128, 64, 128)  # over one key tile
    assert not K.supports(504, 64, 64, 64, 128)  # other widths
    assert not K.supports(504, 6, 128, 64, 128)  # heads not in blocks of 4
    config = M.MoeMlaConfig()
    assert not M.packed_attention_fused(config, 504)  # off the TPU: dense
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert M.packed_attention_fused(config, 504)
    assert not M.packed_attention_fused(config, 16)  # a probe round's length
    toy = M.MoeMlaConfig(qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
    assert not M.packed_attention_fused(toy, 504)  # head widths it does not tile
    assert M.packed_attention_fused(toy, 504, use_flash=True)


def test_sixteen_ranks_add_up_to_the_uncut_layer():
    """The share test: one expert layer's outputs on every rank of the
    deployment, with what all ranks compute alike (residual, attention,
    shared expert) counted once, add up to the uncut reference's layer."""
    from chipbench.architectures.moe_mla_decoder import reference as R
    from pathway_tpu.models import moe_mla as M

    ranks, held = 4, 4
    seed, n, l = 21, 3, 24
    model = tiny_model(layers=2)
    rng = np.random.default_rng(seed)
    ids = rng.integers(4, model["vocab_held"], size=(n, l)).astype(np.int32)
    mask = np.ones((n, l), np.int32)
    mask[2, 17:] = 0

    def reference_layer_out(m):
        """x after the dense and the first expert layer, reference side."""
        params = R.make_params(m, seed)
        fns = R._functions(json.dumps(R._shape_keys(m), sort_keys=True), None)
        enc = R.Encoder(m, seed, max_len=64)
        x = params["embed"][jnp.asarray(ids)].astype(jnp.float32)
        x = fns["dense_layer"](x, jnp.asarray(mask), params["layers"][0])
        layer = params["layers"][1]
        open_x, h, chosen, weights = fns["expert_layer_open"](
            x, jnp.asarray(mask), R.layer_no_experts(layer)
        )
        full = enc._routed(fns, open_x, h, chosen, weights, mask, layer)
        return np.asarray(open_x), np.asarray(full)

    common, uncut = reference_layer_out(
        tiny_model(layers=2, experts_held=ranks * held, expert_offset=0)
    )

    def program_routed_part(rank):
        config = M.MoeMlaConfig(
            vocab_size=model["vocab_held"], hidden=96, layers=2, heads=4,
            q_lora_rank=40, kv_lora_rank=24, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, dense_mlp_dim=160,
            expert_mlp_dim=48, n_routed_experts=16, experts_per_token=4,
            experts_held=held, expert_offset=rank * held, max_len=64,
            dtype="float32", param_dtype="float32",
        )
        from chipbench.reference import weight_seed

        params = M.init_params(jax.random.PRNGKey(weight_seed(seed)), config)
        seg = jnp.asarray(mask)
        x = params["embed"][jnp.asarray(ids)]
        pos = trunk.packed_positions(seg)
        freqs = jnp.asarray(M.yarn_freqs(config))
        for layer in params["layers"][:1]:
            x = x + M._attention(x, layer, config, pos, seg, False, freqs)
            hh = trunk.rms_norm(x, layer["ln2"], config.norm_eps)
            x = x + moe.swiglu(hh, layer["gate"], layer["up"], layer["down"])
        layer = params["layers"][1]
        x = x + M._attention(x, layer, config, pos, seg, False, freqs)
        hh = trunk.rms_norm(x, layer["ln2"], config.norm_eps)
        routed, counts, over = moe.held_experts(
            hh.reshape(n * l, -1), (seg > 0).reshape(-1), layer, config
        )
        assert int(over) == 0
        shared = moe.swiglu(hh, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
        return np.asarray(x + shared), np.asarray(routed).reshape(n, l, -1), int(counts.sum())

    total, pairs = np.array(common), 0
    valid = mask > 0
    for rank in range(ranks):
        alike, routed, held_pairs = program_routed_part(rank)
        # every rank computes it alike, and as the reference does
        np.testing.assert_allclose(alike[valid], common[valid], atol=2e-5)
        total += routed
        pairs += held_pairs
    assert pairs == int(mask.sum()) * model["num_experts_per_tok"]  # every pair on one rank
    np.testing.assert_allclose(total[valid], uncut[valid], atol=5e-5)
    assert np.abs(uncut[valid] - common[valid]).max() > 0.1  # the experts add something


def _expert_layer(seed: int, adversarial: bool):
    """TINY's first expert layer; `adversarial`: the held experts' router
    columns win for every token, so every token holds k pairs here."""
    from pathway_tpu.models import moe_mla as M

    layer = dict(M.init_params(jax.random.PRNGKey(seed), M.TINY)["layers"][1])
    if adversarial:
        bias = jnp.zeros_like(layer["router"]).at[:, : M.TINY.experts_held].set(1.0)
        layer["router"] = layer["router"] * 0.01 + bias
    return layer


def _plain_sum(h, valid, layer, config) -> np.ndarray:
    """The routed part without a buffer: every held expert over every
    token, and of that each selected pair of a valid token, weighted."""
    experts, weights = moe.route(h, layer["router"], config)
    experts, weights = np.asarray(experts), np.asarray(weights)
    want = np.zeros(h.shape, np.float32)
    for e in range(config.experts_held):
        out = moe.swiglu(h, layer["experts_gate"][e], layer["experts_up"][e],
                        layer["experts_down"][e])
        selected = (experts == config.expert_offset + e) & np.asarray(valid)[:, None]
        want += (weights * selected).sum(1)[:, None] * np.asarray(out)
    return want


def test_no_selected_held_pair_is_dropped_and_overflow_is_counted():
    """Adversarial routing: every token's k experts are held here.  The
    buffer of a small slab takes every pair; a buffer forced too small
    counts what it leaves out instead of dropping it silently."""
    from pathway_tpu.models import moe_mla as M

    config = M.TINY
    layer = _expert_layer(9, adversarial=True)
    t, k = 40, config.experts_per_token
    h = jnp.abs(jnp.asarray(np.random.default_rng(9).normal(size=(t, config.hidden)),
                            jnp.float32))
    valid = jnp.arange(t) < 36
    experts, weights = moe.route(h, layer["router"], config)
    assert int((experts < config.experts_held).sum()) == t * k
    y, counts, overflow = moe.held_experts(h, valid, layer, config)
    assert int(counts.sum()) == 36 * k and int(overflow) == 0
    assert moe.pair_capacity(t, config) == 512 >= t * k  # a whole tile, every pair
    assert moe.pair_capacity(500, M.MoeMlaConfig()) == 4096 >= 500 * 8
    assert moe.pair_capacity(14112, M.MoeMlaConfig()) == 14336  # a row a token slot
    # the list of tokens with several pairs: a slot a token up to the
    # buffer's least size, then an eighth of the slots in whole tiles
    assert moe.combine_rows(t, config) == t and moe.combine_rows(4032, M.MoeMlaConfig()) == 4032
    assert moe.combine_rows(14112, M.MoeMlaConfig()) == 2048
    # against the plain sum over the selected pairs
    np.testing.assert_allclose(np.asarray(y), _plain_sum(h, valid, layer, config), atol=2e-4)
    assert not np.asarray(y)[36:].any()  # padding routes nothing
    _, counts, overflow = moe.held_experts(h, valid, layer, config, capacity=100)
    assert int(counts.sum()) == 36 * k and int(overflow) == 36 * k - 100


# routing, token slots, buffer rows, list slots -> multi-pair tokens that
# spill, the groups' layout.  None: the sizes the slab's length gives (a
# 512-row buffer, every token listed).  TINY holds 4 experts: their groups
# begin a tile each where the buffer has the tiles for it
_RETURN_CASES = {
    "random": (False, 40, None, None, False, "packed"),
    "random-list-of-three": (False, 40, None, 3, True, "packed"),
    "adversarial-spill": (True, 40, None, 5, True, "packed"),
    "adversarial-list-of-one": (True, 40, None, 1, True, "packed"),
    "aligned": (False, 300, 2560, None, False, "aligned"),
    "aligned-spill": (False, 300, 2560, 16, True, "aligned"),
    "aligned-two-tiles-a-group": (True, 600, 4608, 128, True, "aligned"),
    "no-room-to-align": (True, 600, 3584, None, False, "packed"),
    "skewed-router-overflows": (True, 300, 1024, 64, True, "packed"),
}


@pytest.mark.parametrize("case", sorted(_RETURN_CASES))
def test_return_to_the_tokens_equals_the_plain_sum(case):
    """`held_experts` (groups begun on tiles or one after the other; a
    pair's weight on the buffer's side, the tokens with several pairs
    summed in a compact list, one gather a token) against the plain sum
    over the selected pairs: with room in the list, and with the list
    forced under the multi-pair count, where the loop over the remaining
    passes runs and no pair is dropped."""
    from pathway_tpu.models import moe_mla as M

    adversarial, t, capacity, listed, spills, layout = _RETURN_CASES[case]
    config = M.TINY
    layer = _expert_layer(9, adversarial)
    rng = np.random.default_rng(17)
    h = jnp.asarray(rng.normal(size=(t, config.hidden)), jnp.float32)
    if adversarial:
        h = jnp.abs(h)
    valid = jnp.asarray(rng.random(t) < 0.9)
    y, counts, overflow, stats = moe.held_experts(
        h, valid, layer, config, capacity, listed=listed, with_stats=True
    )
    experts, _ = moe.route(h, layer["router"], config)
    held_pairs = (np.asarray(experts) < config.experts_held) & np.asarray(valid)[:, None]
    assert int(counts.sum()) == held_pairs.sum()
    assert int(stats["groups_aligned"]) == (layout == "aligned")
    assert int(stats["groups_packed"]) == (layout == "packed")
    assert "fused_returns" not in stats  # 4 of 16 held: the list's return
    if case == "aligned-two-tiles-a-group":
        assert int(counts.min()) > moe.PAIR_ROWS
    if int(overflow) == 0:
        assert int(stats["multi_pair_tokens"]) == (held_pairs.sum(1) > 1).sum()
        np.testing.assert_allclose(
            np.asarray(y), _plain_sum(h, valid, layer, config), atol=2e-4
        )
    else:  # the skewed router's buffer is full: what it leaves out is counted
        assert int(overflow) == held_pairs.sum() - capacity
    assert bool(stats["combine_spills"]) == spills
    assert int(stats["multi_pair_tokens"]) > (listed or t) or not spills
    # neither the list's size nor the groups' layout changes a bit
    other = moe.held_experts(h, valid, layer, config, capacity, listed=t)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(other[0]))
    if int(overflow) == 0:
        packed = moe.held_experts(
            h, valid, layer, config, -(-int(counts.sum()) // 512) * 512, with_stats=True
        )
        assert int(packed[3]["groups_packed"]) == 1 and int(packed[2]) == 0
        np.testing.assert_array_equal(np.asarray(y), np.asarray(packed[0]))


def test_routing_statistics_reach_the_span_record():
    from pathway_tpu.internals import tracing
    from pathway_tpu.models import moe_mla as M
    from pathway_tpu.models.trunk import TransformerLM, model_module

    tracing.reset_spans()
    config = M.TINY
    assert model_module(config) is M
    lm = model_module(config).LM(config, seed=1)
    assert isinstance(lm, TransformerLM)
    rng = np.random.default_rng(1)
    ids = rng.integers(4, config.vocab_size, size=(2, 32)).astype(np.int16)
    seg = np.ones((2, 32), np.int16)
    seg[1, 20:] = 0
    pooled = lm.encode_packed(ids, seg, 2)
    assert pooled.shape == (2, 2, config.hidden)
    lm.count_stats()  # waits for the device; a reading of the record does not
    totals = tracing.spans_status()["totals"]
    k, layers = config.experts_per_token, config.expert_layers
    assert totals["moe.pairs_routed"]["count"] == 52 * k * layers
    held = totals["moe.pairs_held"]["count"]
    assert 0 < held < 52 * k * layers
    assert totals["moe.overflow_pairs"]["count"] == 0
    assert totals["moe.expert_tokens_mean"]["count"] == round(held / config.experts_held)
    assert totals["moe.expert_tokens_max"]["count"] >= totals["moe.expert_tokens_mean"]["count"]
    # the return's and the buffer's counters: a pass is a row group's pass
    # through one expert layer; a small slab lists every token, and its one
    # tile has no room for a tile a group
    assert 0 < totals["moe.multi_pair_tokens"]["count"] < 52 * layers
    assert totals["moe.combine_spills"]["count"] == 0
    assert totals["moe.groups_aligned"]["count"] == 0
    assert totals["moe.groups_packed"]["count"] == layers
    assert totals["moe.fused_returns"]["count"] == 0
    # the unpacked form is the same trunk
    mask = (seg > 0).astype(np.int16)
    np.testing.assert_allclose(
        np.asarray(lm(ids, mask)), np.asarray(pooled)[:, 0], atol=2e-6
    )
    with pytest.raises(NotImplementedError):
        lm.mesh_params(object())


def test_served_path_ingests_and_retrieves_with_the_moe_embedder():
    """FusedEmbedSearch with this configuration: packed ingest, the fused
    search's unpacked queries, and the attention path counted."""
    from pathway_tpu.internals import tracing
    from pathway_tpu.ops.knn import DeviceKnnIndex, FusedEmbedSearch

    tracing.reset_spans()
    enc = program_encoder(tiny_model(), 4)
    index = DeviceKnnIndex(enc.dimension, metric="cos", reserved_space=64)
    fused = FusedEmbedSearch(enc, index)
    docs = texts(10, 4)
    payload, meta = fused.prepare_batch(list(range(10)), docs)
    assert payload[0] == "packed"
    fused.dispatch_batch(payload)
    got = fused.search_texts(docs[:3], 2)
    assert [rows[0][0] for rows in got] == [0, 1, 2]
    assert all(abs(rows[0][1] - 1.0) < 1e-4 for rows in got)
    totals = tracing.spans_status()["totals"]
    assert totals["launch.encode.attn_dense"]["count"] == 1  # off the TPU
    assert totals["moe.pairs_routed"]["count"] > 0
    # the live MFU gauge's FLOPs are this configuration's, not a dense encoder's
    want = meta["real_tokens"] * enc.config.active_flops_per_token(meta["real_tokens"] / 10)
    assert meta["useful_flops"] == pytest.approx(want)


def test_costmodel_takes_the_configurations_active_flops():
    from chipbench.architectures.moe_mla_decoder import costs
    from pathway_tpu.internals import costmodel
    from pathway_tpu.models.moe_mla import MoeMlaConfig

    config = MoeMlaConfig()
    got = costmodel.encoder_flops_for_config(config, 350 * 64, 64)
    model = json.load(open(os.path.join(ROOT, "chipbench/configs/axk1-ep16-docstore.json")))["model"]
    assert got == pytest.approx(64 * costs.flops(model, 350), rel=1e-12)
    assert costmodel.encoder_flops_for_config(config, 0, 0) == 0.0
    # a dense encoder of the same hidden/layers would claim other FLOPs
    assert got != pytest.approx(costmodel.encoder_useful_flops(350 * 64, 64, hidden=7168, layers=6))


def test_costs_integers_are_pinned():
    """A.X-K1's rank of 16 as the configuration file cuts it; the issue's
    arithmetic, to the last digit."""
    from chipbench.architectures.moe_mla_decoder import costs

    model = json.load(open(os.path.join(ROOT, "chipbench/configs/axk1-ep16-docstore.json")))["model"]
    assert costs._attention_params(model) == 101_122_048
    assert costs._expert_params(model) == 44_040_192
    assert costs.layer_params(model) == 3_872_686_080
    assert costs.weight_bytes(model) == 7_745_372_160.0
    assert costs.resident_param_bytes(model) == 8_038_987_776
    assert costs.flops(model, 1) == 2_680_676_352.0
    assert costs.flops(model, 350) == 350 * 2_723_561_472.0
    assert costs.held_pairs_per_token(model) == 0.5
    assert costs.embed_dim(model) == 7168
    assert costs.activation_bytes(model, 350) == 60_211_200.0
    assert costs.mla_attention_flops(model, 350) == 6 * 64 * 320 * 350 * 350
    assert costs.mla_attention_bytes(model, 350) == 2 * 6 * 350 * (64 * 576 + 64)
    assert costs.expert_matmul_flops(model, 1000) == 2 * 1000 * 44_040_192
    assert costs.expert_matmul_bytes(model, 1000, 2) == 2 * 2 * 5 * 12 * 44_040_192 + 4 * 1000 * 7168
    cut = costs.dry_cut(model)
    assert cut["layers"] == 2 and {k: v for k, v in cut.items() if k != "layers"} == {
        k: v for k, v in model.items() if k != "layers"
    }


def test_yarn_ladder_and_scale():
    from chipbench.architectures.moe_mla_decoder import reference as R
    from pathway_tpu.models import moe_mla as M

    model = tiny_model(qk_rope_head_dim=64)
    freqs = R.yarn_freqs(model)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert freqs.shape == (32,)
    np.testing.assert_allclose(freqs[:10], plain[:10])  # fast pairs: as plain RoPE
    np.testing.assert_allclose(freqs[-8:], plain[-8:] / 32)  # slow pairs: interpolated
    assert (np.diff(freqs) < 0).all()
    np.testing.assert_allclose(M.yarn_freqs(M.MoeMlaConfig()), freqs, rtol=1e-6)
    m = 0.1 * np.log(32.0) + 1.0
    assert R.softmax_scale(model | {"qk_nope_head_dim": 128}) == pytest.approx(192 ** -0.5 * m * m)
    assert M.MoeMlaConfig().sm_scale == pytest.approx(192 ** -0.5 * m * m)


# the three trunks that call `pair_capacity` / `combine_rows`, at the slabs
# their cells dispatch and read back: token slots -> buffer rows, list slots.
# A.X-K1's and MiMo-V2.5's are what they were before the rule learned of
# top-1 with every expert held (PR 42): their programs' shapes, their
# `memory_peak_bytes` and their compile cache entries hang on these numbers
_BUFFER_PINS = {
    "axk1-ingest-row-group": ("moe_mla", 14112, 14336, 2048),
    "axk1-query-slab": ("moe_mla", 4096, 4096, 4096),
    "axk1-probe-slab": ("moe_mla", 256, 2048, 256),
    "mimo-ingest-row": ("moe_hybrid", 24576, 24576, 3072),
    "mimo-query-row": ("moe_hybrid", 16384, 16384, 2048),
    "mimo-probe-slab": ("moe_hybrid", 1024, 4096, 1024),
    "zaya-ingest-slab": ("zaya", 28224, 36864, 0),
    "zaya-query-slab": ("zaya", 4096, 12288, 0),
    "zaya-probe-slab": ("zaya", 256, 8704, 0),
    # every expert held at top-8 (PR 44): the rows are every pair and a tile
    # a held expert; the list has a slot a token and cannot spill
    "laguna-ingest-row": ("moe_hybrid", 23552, 319488, 23552, "laguna"),
    "laguna-query-row-group": ("moe_hybrid", 16384, 262144, 16384, "laguna"),
    "laguna-probe-slab": ("moe_hybrid", 1024, 139264, 1024, "laguna"),
}
# the routing a configuration changes from its trunk's defaults
_ROUTING = {"laguna": dict(n_routed_experts=256, experts_held=256, experts_per_token=8)}


@pytest.mark.parametrize("case", sorted(_BUFFER_PINS))
def test_the_buffer_rule_is_pinned_at_the_cells_slabs(case):
    """One rule from the slots, k and held / routed: a row a token slot
    where the expected load is under that (the two ranks that hold a
    sixteenth of the experts at top-8), and every pair there can be and a
    tile a held expert where it fills them (top-1 and top-8 with every
    expert held)."""
    import importlib

    module, tokens, rows, listed, *routing = _BUFFER_PINS[case]
    trunk = importlib.import_module(f"pathway_tpu.models.{module}")
    config = {"moe_mla": "MoeMlaConfig", "moe_hybrid": "MoeHybridConfig",
              "zaya": "ZayaConfig"}[module]
    published = getattr(trunk, config)(**_ROUTING.get(*routing, {}) if routing else {})
    assert moe.pair_capacity(tokens, published) == rows
    assert moe.combine_rows(tokens, published) == listed
    assert rows % moe.PAIR_ROWS == 0


# routed experts, held, k -> whether the return is the fused weighted sum
_FUSED_RETURNS = {
    "laguna-256-of-256-top8": (256, 256, 8, True),
    "axk1-12-of-192-top8": (192, 12, 8, False),
    "mimo-16-of-256-top8": (256, 16, 8, False),
    "zaya-16-of-16-top1": (16, 16, 1, False),
    # a held pair a token expected, but not every expert held: the list's
    "tiny-4-of-16-top4": (16, 4, 4, False),
}


@pytest.mark.parametrize("case", sorted(_FUSED_RETURNS))
def test_the_fused_return_engages_with_every_expert_held_at_top_k_over_one(case):
    from pathway_tpu.models import moe_mla as M

    routed, held, k, want = _FUSED_RETURNS[case]
    config = M.MoeMlaConfig(n_routed_experts=routed, experts_held=held, experts_per_token=k)
    assert moe.returns_fused(config) is want


@pytest.mark.parametrize("tokens", [40, 900])
def test_top_one_with_every_expert_held_aligns_its_groups_and_lists_nothing(tokens):
    """`held_experts` under a routing handed in by the trunk (top-1 of 8
    experts and "skip", all 8 held): every group begins a tile, no token
    has two pairs, nothing is dropped, "skip" and the padding compute
    nothing, and the return is the plain sum."""
    from pathway_tpu.models import zaya

    config = zaya.TINY
    layer = zaya.init_params(jax.random.PRNGKey(2), config)["layers"][0]
    rng = np.random.default_rng(tokens)
    h = jnp.asarray(rng.normal(size=(tokens, config.hidden)), jnp.float32)
    valid = jnp.asarray(rng.random(tokens) < 0.9)
    experts, weights, _ = zaya.route(
        h, jnp.zeros((tokens, config.router_hidden)), layer, config
    )
    y, counts, overflow, stats = moe.held_experts(
        h, valid, layer, config, with_stats=True, routing=(experts, weights)
    )
    chosen = np.asarray(experts)[:, 0]
    real = np.asarray(valid)
    skip = config.n_routed_experts
    assert 0 < (chosen == skip).sum() < tokens
    np.testing.assert_array_equal(
        counts, np.bincount(chosen[real & (chosen < skip)], minlength=skip)
    )
    assert int(overflow) == 0 and int(stats["multi_pair_tokens"]) == 0
    assert int(stats["combine_spills"]) == 0 and int(stats["groups_aligned"]) == 1
    assert "fused_returns" not in stats  # top-1: one inverse permutation
    assert moe.pair_capacity(tokens, config) >= tokens + config.experts_held * moe.PAIR_ROWS
    want = np.zeros((tokens, config.hidden), np.float32)
    for e in range(skip):
        out = moe.swiglu(h, layer["experts_gate"][e], layer["experts_up"][e],
                        layer["experts_down"][e])
        picked = (chosen == e) & real
        want += (np.asarray(weights)[:, 0] * picked)[:, None] * np.asarray(out)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    assert not np.asarray(y)[~real | (chosen == skip)].any()
    # a buffer without the room: the groups follow each other, same numbers
    packed = moe.held_experts(
        h, valid, layer, config, -(-tokens // 512) * 512, with_stats=True,
        routing=(experts, weights),
    )
    assert int(packed[3]["groups_packed"]) == 1 and int(packed[2]) == 0
    np.testing.assert_array_equal(np.asarray(y), np.asarray(packed[0]))
