"""The shortcut-connected MoE trunk (`models/longcat.py`, one expert-parallel
rank of LongCat-Flash's double layer) against its plain reference
(`chipbench/architectures/longcat_decoder/reference.py`, which imports
nothing of the program), at tiny sizes on the CPU, seeded; the expert
layer's softmax router and zero-compute experts (`models/experts.py`);
and the latent attention that moved below the trunks (`models/mla.py`)
against A.X-K1's forward as it was."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models import experts as moe
from pathway_tpu.models import trunk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "chipbench/configs/longcat-flash-ep32-docstore.json")


@pytest.fixture(autouse=True)
def _count_every_dispatch():
    """A packed LM counts a dispatch's statistics into the span record at
    the next reading of it: count them here, so that no dispatch of this
    file lands in another test's record."""
    yield
    for lm in list(trunk._LIVE):
        lm.count_stats()


def tiny_model(**changes) -> dict:
    """The configuration file's `model` group at toy widths, under the keys
    the architecture's three files read.  The router's 24 outputs: 16
    routed experts and 8 zero-compute ones, top-6, 4 held here; beta as
    published (at 24 outputs it moves few choices)."""
    model = dict(json.load(open(CONFIG))["model"])
    model.update(
        hidden_size=96, num_attention_heads=4, q_lora_rank=40, kv_lora_rank=24,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, ffn_hidden_size=160,
        expert_ffn_hidden_size=48, n_routed_experts=16, zero_expert_num=8, moe_topk=6,
        layers=2, experts_held=4, expert_offset=0, vocab_held=512,
        dtype="float32", param_dtype="float32",
    )
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
    from chipbench.architectures.longcat_decoder import program
    from pathway_tpu.models import minilm

    minilm._model_cache.clear()
    return program.embedder(model, STORE, seed).encoder


def reference_encoder(model: dict, seed: int):
    from chipbench.architectures.longcat_decoder.reference import Encoder

    return Encoder(model, seed, max_len=STORE["max_len"])


def gap(a: np.ndarray, b: np.ndarray) -> float:
    """Widest |difference| of two sets of unit vectors' coordinates, in
    units of a coordinate's own scale (1/sqrt(d))."""
    return float(np.abs(a - b).max() * np.sqrt(a.shape[1]))


# Tolerances, in coordinate scales (`gap`), as tests/test_moe_mla.py sets
# its own, from readings on the CPU over seeds 3, 7, 11, 13, 99 and
# 2030405060.  float32: both sides compute in f32 and differ in summation
# order only: 1.9e-6 to 2.5e-6 is read, the tolerance is eight times the
# widest.  bfloat16 (parameters rounded to bf16 on both sides, the program
# also computes in bf16, so a token near a tie of the router's top-k may
# take another expert): 0.033 to 0.118 is read, the fp8 control reads 0.40
# to 0.51 and int8 0.10 to 0.14; 0.25 lies between the program's widest
# and the fp8 control's smallest, with room on both sides.
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


def test_beta_selects_in_program_and_reference_alike(monkeypatch):
    """The selection bias enters both sides: with a beta wide enough to
    move TINY's choices the program agrees with the reference, and a
    reference that leaves beta out does not.  (At the published widths
    beta moves most tokens' choice but hardly the pooled vectors: PERF.md
    section 7.)"""
    from chipbench.architectures.longcat_decoder import program
    from pathway_tpu.models import longcat as L

    monkeypatch.setattr(L, "BIAS_STD", 0.05)
    monkeypatch.setitem(program.READINGS, "bias_std", 0.05)
    seed, docs = 17, texts(12, 17)
    got = program_encoder(tiny_model(bias_std=0.05), seed).encode_packed(docs)
    assert gap(got, reference_encoder(tiny_model(bias_std=0.05), seed).embed(docs)) < F32_TOL
    assert gap(got, reference_encoder(tiny_model(bias_std=0.0), seed).embed(docs)) > 10 * F32_TOL


def test_packed_causal_equals_each_document_alone_and_unpacked():
    """Packing changes nothing a document can see: positions restart, the
    mask is causal within the segment, routing is per token."""
    from pathway_tpu.models import longcat as L

    config = L.TINY
    params = L.init_params(jax.random.PRNGKey(5), config)
    rng = np.random.default_rng(5)
    l = 48
    ids = rng.integers(4, config.vocab_size, size=(2, l)).astype(np.int32)
    seg = np.zeros((2, l), np.int32)
    docs = [(0, 0, 20), (0, 20, 45), (1, 0, 40)]  # row, from, to
    seg[0, 0:20], seg[0, 20:45], seg[1, 0:40] = 1, 2, 1
    packed, stats = L.forward(
        params, config, ids, None, seg=jnp.asarray(seg), max_segments=4, with_stats=True,
    )
    assert int(stats["tokens"]) == 85 and int(stats["overflow"].sum()) == 0
    assert stats["expert_tokens"].shape == (config.layers, config.experts_held)
    assert stats["zero_pairs"].shape == (config.layers,)
    for (row, lo, hi), slot in zip(docs, (0, 1, 0)):
        one = np.zeros((1, l), np.int32)
        mask = np.zeros((1, l), np.int32)
        one[0, : hi - lo], mask[0, : hi - lo] = ids[row, lo:hi], 1
        alone = L.forward(params, config, jnp.asarray(one), jnp.asarray(mask))
        np.testing.assert_allclose(np.asarray(alone)[0], np.asarray(packed)[row, slot], atol=2e-6)
    # causal: a document's later tokens do not reach its earlier ones
    cut = np.array(ids)
    cut[1, 30:40] = 7
    prefix_seg = np.array(seg)
    prefix_seg[1, 30:] = 0
    a = L.forward(params, config, ids, None, seg=jnp.asarray(prefix_seg), max_segments=4)
    b = L.forward(params, config, cut, None, seg=jnp.asarray(prefix_seg), max_segments=4)
    np.testing.assert_allclose(np.asarray(a)[1, 0], np.asarray(b)[1, 0], atol=1e-7)


def _program_config(model: dict, **changes):
    from chipbench.architectures.longcat_decoder import program

    return program.config_of(dict(model, **changes), STORE)


def _reference_double_layer(model: dict, seed: int, x, n_real: int, experts=None):
    """x after the reference's double layer 0, made with `experts` (global
    indices; default the held ones)."""
    from chipbench.architectures.longcat_decoder import reference as R

    m = R._shape_keys(model)
    fns = R._functions(json.dumps(m, sort_keys=True), None)
    return np.asarray(R.double_layer(fns, [x], [n_real], R.make_layer(m, seed, 0, experts))[0])


def _program_pieces(config, seed: int, ids: np.ndarray):
    """The program's double layer 0 over one text: (x entering it, what
    every rank computes alike, this rank's held experts' part)."""
    from chipbench.reference import weight_seed
    from pathway_tpu.models import longcat as L

    params = L.init_params(jax.random.PRNGKey(weight_seed(seed)), config)
    seg = jnp.ones((1, len(ids)), jnp.int32)
    x = params["embed"][jnp.asarray(ids)[None]]
    alike, routed, stats = L._double_layer(
        x, params["layers"][0], config, trunk.packed_positions(seg), seg, False,
        jnp.ones((len(ids),), bool),
    )
    assert int(stats["overflow"]) == 0
    return np.asarray(x[0]), np.asarray(alike[0]), np.asarray(routed), stats


def test_the_ranks_shares_add_up_to_the_uncut_double_layer():
    """The share test: every rank of the expert split (4 ranks of 4 of the
    16 routed experts) computes its held experts' part; with what all ranks
    compute alike (attention, both dense FFNs, the zero-compute experts)
    counted once, the parts add up to the uncut reference's double layer."""
    ranks, held, seed = 4, 4, 21
    model = tiny_model(layers=1)
    ids = np.random.default_rng(seed).integers(4, 512, size=30).astype(np.int32)
    total, pairs, zero_pairs = None, 0, set()
    for rank in range(ranks):
        config = _program_config(model, experts_held=held, expert_offset=rank * held)
        x, alike, routed, stats = _program_pieces(config, seed, ids)
        if total is None:
            total, common = np.array(alike), alike
        np.testing.assert_allclose(alike, common, atol=1e-6)  # every rank alike
        total += routed
        pairs += int(stats["expert_tokens"].sum())
        zero_pairs.add(int(stats["zero_pairs"]))
    uncut = _reference_double_layer(model, seed, jnp.asarray(x), len(ids), experts=range(16))
    np.testing.assert_allclose(total, uncut, atol=5e-5)
    assert len(zero_pairs) == 1  # the zero-compute pairs are every rank's alike
    # every selected pair lies on exactly one rank or on a zero-compute expert
    assert pairs + zero_pairs.pop() == len(ids) * model["moe_topk"]
    assert np.abs(uncut - common).max() > 0.05  # the routed experts add something


def test_the_shortcut_joins_after_the_second_sublayer_pair():
    """A double layer that adds the MoE branch after the first sublayer
    pair (where FFN_0's output joins) is another model: it fails the
    comparison by orders of magnitude over the float32 tolerance, where the
    program's order agrees."""
    from pathway_tpu.models import longcat as L

    seed = 8
    model = tiny_model(layers=1)
    config = _program_config(model)
    ids = np.random.default_rng(seed).integers(4, 512, size=30).astype(np.int32)
    x, alike, routed, _ = _program_pieces(config, seed, ids)
    want = _reference_double_layer(model, seed, jnp.asarray(x), len(ids))
    np.testing.assert_allclose(alike + routed, want, atol=2e-5)

    from chipbench.reference import weight_seed

    layer = L.init_params(jax.random.PRNGKey(weight_seed(seed)), config)["layers"][0]
    c, seg = config, jnp.ones((1, len(ids)), jnp.int32)
    pos = trunk.packed_positions(seg)
    scales = (c.q_scale, c.kv_scale)
    xx = jnp.asarray(x)[None]
    a0 = xx + L._attention(xx, layer["attn"][0], c, pos, seg, False, None, *scales)
    h0 = trunk.rms_norm(a0, layer["ffn"][0]["ln"], c.norm_eps)
    routing = moe.route(h0[0], layer["router"], c, layer["router_bias"], softmax=True,
                        normalise=False)
    m = moe.zero_expert_part(h0[0], *routing, c.n_routed_experts) + jnp.asarray(routed)
    f0 = layer["ffn"][0]
    b0 = a0 + moe.swiglu(h0, f0["gate"], f0["up"], f0["down"]) + m  # joined too early
    a1 = b0 + L._attention(b0, layer["attn"][1], c, pos, seg, False, None, *scales)
    f1 = layer["ffn"][1]
    early = a1 + moe.swiglu(trunk.rms_norm(a1, f1["ln"], c.norm_eps), f1["gate"], f1["up"],
                            f1["down"])
    assert np.abs(np.asarray(early[0]) - want).max() > 100 * 2e-5


def test_zero_compute_pairs_add_w_h0_and_are_never_held():
    """Ids at and above the routed experts' count are the identity: the
    pairs on them add w * h, are never bucketed or counted as held, and a
    router that picks only them leaves the held experts idle."""
    from pathway_tpu.models import longcat as L

    config = L.TINY
    layer = dict(L.init_params(jax.random.PRNGKey(4), config)["layers"][0])
    rng = np.random.default_rng(4)
    t, n_routed = 64, config.n_routed_experts
    h = jnp.asarray(rng.normal(size=(t, config.hidden)), jnp.float32)
    valid = jnp.asarray(rng.random(t) < 0.9)
    experts, weights = moe.route(h, layer["router"], config, layer["router_bias"],
                                 softmax=True, normalise=False)
    chosen, w = np.asarray(experts), np.asarray(weights)
    assert chosen.max() < config.router_outputs and (chosen >= n_routed).any()
    # the weights are the scaled softmax scores, not renormalised
    logits = np.asarray(h) @ np.asarray(layer["router"])
    p = np.exp(logits - logits.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    np.testing.assert_allclose(w, config.routed_scaling_factor * np.take_along_axis(p, chosen, 1),
                               rtol=1e-5)
    assert (w.sum(1) < config.routed_scaling_factor).all()
    zero = moe.zero_expert_part(h, experts, weights, n_routed)
    want = (w * (chosen >= n_routed)).sum(1)[:, None] * np.asarray(h)
    np.testing.assert_allclose(np.asarray(zero), want, rtol=1e-6, atol=1e-6)
    _, counts, overflow = moe.held_experts(h, valid, layer, config, routing=(experts, weights))
    held = (chosen >= config.expert_offset) & (chosen < config.expert_offset + config.experts_held)
    assert int(counts.sum()) == (held & np.asarray(valid)[:, None]).sum()
    assert int(overflow) == 0
    # a router that picks zero-compute experts only
    picks = n_routed + np.arange(config.experts_per_token) % config.zero_experts
    only_zero = jnp.asarray(np.tile(picks, (t, 1)), jnp.int32)
    y, counts, overflow = moe.held_experts(h, valid, layer, config, routing=(only_zero, weights))
    assert int(counts.sum()) == 0 and int(overflow) == 0 and not np.asarray(y).any()
    np.testing.assert_allclose(
        np.asarray(moe.zero_expert_part(h, only_zero, weights, n_routed)),
        w.sum(1)[:, None] * np.asarray(h), rtol=1e-6, atol=1e-6,
    )


def test_softmax_routing_leaves_the_sigmoid_form_as_it_was():
    """`route`'s default is the sigmoid form, normalised and scaled as it
    was; the softmax form without renormalisation is another choice of the
    caller's."""
    from pathway_tpu.models import moe_mla as M

    config = M.TINY
    layer = M.init_params(jax.random.PRNGKey(2), config)["layers"][1]
    h = jnp.asarray(np.random.default_rng(2).normal(size=(20, config.hidden)), jnp.float32)
    experts, weights = moe.route(h, layer["router"], config)
    s = jax.nn.sigmoid(h @ layer["router"])
    top, want = jax.lax.top_k(s, config.experts_per_token)
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(weights),
        np.asarray(config.routed_scaling_factor * top / top.sum(-1, keepdims=True)),
    )
    _, soft = moe.route(h, layer["router"], config, softmax=True, normalise=False)
    assert float(np.asarray(soft).sum(1).max()) < config.routed_scaling_factor


def test_routing_statistics_and_zero_pairs_reach_the_span_record():
    from pathway_tpu.internals import tracing
    from pathway_tpu.models import longcat as L
    from pathway_tpu.models.trunk import TransformerLM, model_module

    tracing.reset_spans()
    config = L.TINY
    assert model_module(config) is L
    lm = model_module(config).LM(config, seed=1)
    assert isinstance(lm, TransformerLM) and type(lm) is trunk.PackedTrunkLM
    rng = np.random.default_rng(1)
    ids = rng.integers(4, config.vocab_size, size=(2, 32)).astype(np.int16)
    seg = np.ones((2, 32), np.int16)
    seg[1, 20:] = 0
    pooled = lm.encode_packed(ids, seg, 2)
    assert pooled.shape == (2, 2, config.hidden)
    lm.count_stats()
    totals = tracing.spans_status()["totals"]
    k, layers = config.experts_per_token, config.layers
    routed = totals["moe.pairs_routed"]["count"]
    assert routed == 52 * k * layers
    held = totals["moe.pairs_held"]["count"]
    zero = totals["longcat.zero_pairs"]["count"]
    assert 0 < held and 0 < zero and held + zero < routed
    # the router's outputs are a third zero-compute experts: near a third of the picks
    assert 0.15 < zero / routed < 0.55
    assert totals["moe.overflow_pairs"]["count"] == 0
    mask = (seg > 0).astype(np.int16)
    np.testing.assert_allclose(np.asarray(lm(ids, mask)), np.asarray(pooled)[:, 0], atol=2e-6)
    with pytest.raises(NotImplementedError, match="expert exchange"):
        lm.mesh_params(object())


def test_served_path_ingests_and_retrieves_with_the_longcat_embedder():
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
    want = meta["real_tokens"] * enc.config.active_flops_per_token(meta["real_tokens"] / 10)
    assert meta["useful_flops"] == pytest.approx(want)


def test_costs_integers_are_pinned():
    """The rank of 32 as the configuration file cuts it; the arithmetic of
    the configuration's `deployment`, to the last digit, and the program's
    own FLOPs a token."""
    from chipbench.architectures.longcat_decoder import costs
    from pathway_tpu.internals import costmodel
    from pathway_tpu.models.longcat import LongcatConfig

    model = json.load(open(CONFIG))["model"]
    assert costs._attention_params(model) == 90_570_752
    assert costs._ffn_params(model) == 226_492_416
    assert costs._expert_params(model) == 37_748_736
    assert costs.router_outputs(model) == 768
    assert costs.held_pairs_per_token(model) == 0.25
    assert costs.layer_params(model) == 4_971_413_504
    assert costs.weight_bytes(model) == 9_942_827_008.0
    assert costs.resident_param_bytes(model) == 10_144_178_176
    assert costs.embed_dim(model) == 6144
    per_token = 2 * 4 * (2 * 90_570_752 + 2 * 226_492_416 + 6144 * 768 + 0.25 * 37_748_736)
    assert costs.flops(model, 1) == per_token + 2 * 4 * 64 * 320
    assert costs.flops(model, 350) == 350 * per_token + 2 * 4 * 64 * 320 * 350 * 350
    assert costs.flops(model, 350) / 350 == pytest.approx(5.2436e9, rel=1e-4)
    assert costs.activation_bytes(model, 350) == 2 * 2 * 350 * 6144 * 16
    assert costs.mla_attention_bytes(model, 350) == 2 * 2 * 4 * 350 * (64 * 576 + 64)
    assert costs.expert_matmul_flops(model, 1000) == 2 * 1000 * 37_748_736
    assert costs.expert_matmul_bytes(model, 1000, 2) == 2 * 2 * 4 * 16 * 37_748_736 + 4 * 1000 * 6144
    cut = costs.dry_cut(model)
    assert cut["layers"] == 1 and {k: v for k, v in cut.items() if k != "layers"} == {
        k: v for k, v in model.items() if k != "layers"
    }
    # the dense FFNs and MLA's projections are the work
    ffn = 2 * 4 * 2 * 226_492_416 / (costs.flops(model, 350) / 350)
    assert ffn == pytest.approx(0.691, abs=0.001)
    config = LongcatConfig()
    assert costmodel.encoder_flops_for_config(config, 350 * 64, 64) == pytest.approx(
        64 * costs.flops(model, 350), rel=1e-12
    )
    # the buffer of the ingest slab's row group: a row a token slot, an
    # eighth in the list of tokens with two held pairs or more
    assert moe.pair_capacity(14112, config) == 14336
    assert moe.combine_rows(14112, config) == 2048
    assert not moe.returns_fused(config)


def test_the_configuration_is_read_as_published_or_refused():
    from chipbench.architectures.longcat_decoder import program
    from chipbench.architectures.longcat_decoder import reference as R

    model = json.load(open(CONFIG))["model"]
    config = program.config_of(model, {"max_len": 512})
    assert (config.hidden, config.layers, config.heads, config.ffn_dim) == (6144, 4, 64, 12288)
    assert (config.n_routed_experts, config.zero_experts, config.experts_per_token) == (512, 256, 12)
    assert config.q_scale == 2.0 and config.kv_scale == pytest.approx(12 ** 0.5)
    assert config.sm_scale == pytest.approx(192 ** -0.5)
    for key, value in (("zero_expert_type", "copy"), ("attention_method", "GQA"),
                       ("norm_topk_prob", True), ("bias_std", 0.02),
                       ("mla_scale_kv_lora", False)):
        with pytest.raises(ValueError, match=key):
            program.config_of(dict(model, **{key: value}), {"max_len": 512})
    with pytest.raises(ValueError, match="zero_expert_type"):
        R.Encoder(dict(model, zero_expert_type="copy"), 1, max_len=64)
    with pytest.raises(ValueError, match="rope_scaling"):
        R.Encoder(dict(model, rope_scaling={"type": "yarn"}), 1, max_len=64)


# -- the latent attention below the trunks ---------------------------------------


def _attention_before_the_move(x, layer, config, pos, seg, fused, freqs):
    """`moe_mla._attention` as it stood before it moved to `models/mla.py`:
    the guard that A.X-K1's forward is the one it was, bit for bit."""
    from pathway_tpu.models.mla import _mla_segment_attention
    from pathway_tpu.ops.kernels.mla_attention import mla_segment_attention

    c = config
    b, l, _ = x.shape
    dt = x.dtype
    h = trunk.rms_norm(x, layer["ln1"], c.norm_eps)
    c_q = trunk.rms_norm(h @ layer["wq_a"].astype(dt), layer["q_ln"], c.norm_eps)
    q_nope = c_q @ layer["wq_b_nope"].astype(dt)
    q_rope = c_q @ layer["wq_b_rope"].astype(dt)
    kv_a = h @ layer["wkv_a"].astype(dt)
    c_kv = trunk.rms_norm(kv_a[..., : c.kv_lora_rank], layer["kv_ln"], c.norm_eps)
    k_nope = c_kv @ layer["wk_b"].astype(dt)
    v = c_kv @ layer["wv_b"].astype(dt)

    def rotate(a, n_heads):
        flat = a.reshape(b * l, n_heads, 1, c.qk_rope_head_dim)
        out = trunk.rope(flat, pos.reshape(b * l, 1), c.rope_theta, freqs=freqs,
                         interleaved=True)
        return out.reshape(b, l, n_heads * c.qk_rope_head_dim)

    q_rope = rotate(q_rope, c.heads)
    k_rope = rotate(kv_a[..., c.kv_lora_rank:], 1)
    if fused:
        ctx = mla_segment_attention(q_nope, q_rope, k_nope, k_rope, v, seg, sm_scale=c.sm_scale)
    else:
        ctx = _mla_segment_attention(q_nope, q_rope, k_nope, k_rope, v, seg, c.sm_scale, c.heads)
    return ctx @ layer["wo"].astype(dt)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_moved_latent_attention_gives_axk1s_forward_bit_for_bit(dtype, monkeypatch):
    """With both LoRA scales at 1.0 the shared `mla._attention` is the
    attention A.X-K1's trunk ran before it moved: the same bits on the dense
    path and on the interpreted kernel, and the same pooled vectors from
    `moe_mla.forward`; a scale other than 1.0 changes them."""
    import dataclasses

    from pathway_tpu.models import mla
    from pathway_tpu.models import moe_mla as M

    config = dataclasses.replace(M.TINY, dtype=dtype, param_dtype=dtype,
                                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
    params = M.init_params(jax.random.PRNGKey(3), config)
    rng = np.random.default_rng(3)
    ids = rng.integers(4, config.vocab_size, size=(2, 40)).astype(np.int32)
    seg = np.zeros((2, 40), np.int32)
    seg[0, :25], seg[0, 25:], seg[1, :33] = 1, 2, 1
    seg = jnp.asarray(seg)
    x = params["embed"][jnp.asarray(ids)].astype(trunk._dtype(dtype))
    pos, freqs = trunk.packed_positions(seg), jnp.asarray(M.yarn_freqs(config))
    layer = params["layers"][0]
    for fused in (False, True):
        got = mla._attention(x, layer, config, pos, seg, fused, freqs, 1.0, 1.0)
        want = _attention_before_the_move(x, layer, config, pos, seg, fused, freqs)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    scaled = mla._attention(x, layer, config, pos, seg, False, freqs, 2.0, 1.0)
    assert not np.array_equal(np.asarray(scaled), np.asarray(got))
    assert M._attention is mla._attention
    now = M.forward(params, config, ids, None, seg=seg, max_segments=2, use_flash=False)
    monkeypatch.setattr(M, "_attention", _attention_before_the_move)
    before = M.forward(params, config, ids, None, seg=seg, max_segments=2, use_flash=False)
    np.testing.assert_array_equal(np.asarray(now), np.asarray(before))
