"""The compressed-convolutional-attention MoE trunk (`models/zaya.py`, one
pipeline stage: two causal convolutions and a shifted value along a packed
row, a router that carries its state from layer to layer, a learned merge)
against its plain reference
(`chipbench/architectures/zaya_decoder/reference.py`, which imports nothing
of the program and packs nothing, so no seam can exist there), each
mechanism left out in turn (of the dense definition and of the kernels),
its two kernels against their dense definitions, the seam rule and its
second copy, the one gate of both kernels, the shares of an expert layer,
its counters: at tiny sizes on the CPU, seeded."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models import experts as moe
from pathway_tpu.models import trunk, zaya
from pathway_tpu.models.tokenizer import PACK_MAX_SEGMENTS, encode_batch, pack_batch
from pathway_tpu.models.trunk import model_module, packed_positions
from pathway_tpu.ops import kernels
from pathway_tpu.ops.kernels import cca_attention as kernel
from pathway_tpu.ops.kernels import cca_latent as latent
from pathway_tpu.ops.kernels.hybrid_attention import rope_tables


def tiny_model(**changes) -> dict:
    """A configuration's `model` group at toy widths (the head's width and
    its rotated share stay the published ones: the kernel's tiling and the
    RoPE tables are written for them), under the keys the architecture's
    three files read."""
    model = {
        "name": "tiny-zaya", "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 128, "rotary_dim": 64,
        "partial_rotary_factor": 0.5, "cca_time0": 2, "cca_time1": 2,
        "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                       "rope_theta": 5000000, "rope_type": "default"}},
        "rms_norm_eps": 1e-5, "moe_intermediate_size": 32, "num_experts": 8,
        "num_experts_per_tok": 1, "router_hidden_size": 32, "hidden_act": "silu",
        "sliding_window": None, "attention_bias": False, "layer_types": ["hybrid"] * 40,
        "vocab_size": 4096, "num_hidden_layers": 40, "layers": 3, "experts_held": 8,
        "expert_offset": 0, "vocab_held": 512, "pp_size": 2, "max_len": 256,
        "pooling": "mean", "dtype": "float32", "param_dtype": "float32",
        "tau_mean": zaya.TAU_MEAN, "tau_std": zaya.TAU_STD, "alpha_std": zaya.ALPHA_STD,
        "gamma_mean": zaya.GAMMA_MEAN, "gamma_std": zaya.GAMMA_STD,
        "beta_std": zaya.BETA_STD, "conv_bias_std": zaya.CONV_BIAS_STD,
    }
    model.update(changes)
    return model


STORE = {"max_len": 256}


def text_of(words: int, seed: int) -> str:
    """A text of exactly `words` words: with [CLS] and [SEP], words + 2 tokens."""
    rng = np.random.default_rng([words, seed])
    return " ".join(f"w{int(x)}" for x in rng.integers(0, 5000, size=words))


def program_encoder(model: dict, seed: int):
    from chipbench.architectures.zaya_decoder import program
    from pathway_tpu.models import minilm

    minilm._model_cache.clear()
    return program.embedder(model, STORE, seed).encoder


def reference_vectors(model: dict, seed: int, texts: list, **kwargs) -> np.ndarray:
    from chipbench.architectures.zaya_decoder.reference import Encoder

    return Encoder(model, seed, max_len=STORE["max_len"]).embed(texts, **kwargs)


# eighteen documents of 4 to 102 tokens: first-fit into rows of 128 slots
# packs them two to five a row, so every row has seams and most documents
# begin off any tile
TEXTS = [text_of(w, i) for i, w in enumerate(
    (100, 17, 68, 43, 5, 90, 30, 2, 55, 55, 12, 80, 25, 20, 16, 22, 14, 18)
)]

# float32 program against the float32 reference at `highest`: what
# separates them is the order of the sums (packed rows, the kernel's
# blocks, grouped matmuls), a few ulps of 1e-7 through three layers: 2e-5
# on a unit vector's components leaves a factor of ten
F32_TOL = 2e-5


def packed(enc):
    ids, seg, slots = pack_batch(enc.tokenizer, TEXTS, max_len=256, token_budget=128)
    return jnp.asarray(ids, jnp.int32), jnp.asarray(seg, jnp.int32), slots


def packed_vectors(enc, params=None, **kwargs) -> np.ndarray:
    ids, seg, slots = packed(enc)
    pooled = zaya.forward(
        enc.lm.params if params is None else params, enc.config, ids, None,
        seg=seg, max_segments=PACK_MAX_SEGMENTS, **kwargs,
    )
    return np.stack([np.asarray(pooled)[r, s] for r, s in slots])


@pytest.mark.parametrize("use_flash", [False, True], ids=["dense", "kernel-interpreted"])
def test_the_packed_program_agrees_with_the_plain_reference(use_flash):
    """`use_flash=True` is the fused path whole: the latent through
    `cca_latent`, the attention through `cca_attention`, both interpreted."""
    model = tiny_model()
    enc = program_encoder(model, seed=7)
    ids, seg, _ = packed(enc)
    per_row = [len(set(row[row > 0])) for row in np.asarray(seg) if row.any()]
    assert ids.shape[1] == 128 and min(per_row) == 2 and max(per_row) == 5
    got = packed_vectors(enc, use_flash=use_flash)
    want = reference_vectors(model, 7, TEXTS)
    np.testing.assert_allclose(got, want, atol=F32_TOL)
    np.testing.assert_allclose(np.linalg.norm(got, axis=1), 1.0, atol=1e-5)


def test_the_unpacked_form_is_the_packed_one_and_row_groups_change_nothing(monkeypatch):
    """`encode` (one text a row, the read-back's path) against the
    reference, and the packed slab once whole and once as groups of one
    row: rows are whole, so a group's first slot is a row's first slot."""
    model = tiny_model()
    enc = program_encoder(model, seed=11)
    want = reference_vectors(model, 11, TEXTS[:4])
    np.testing.assert_allclose(enc.encode(TEXTS[:4]), want, atol=F32_TOL)
    ids, seg, slots = packed(enc)
    whole, whole_stats = zaya.forward(
        enc.lm.params, enc.config, ids, None, seg=seg,
        max_segments=PACK_MAX_SEGMENTS, with_stats=True,
    )
    monkeypatch.setattr(trunk, "row_chunks", lambda rows, length, cap: rows)
    grouped, stats = zaya.forward(
        enc.lm.params, enc.config, ids, None, seg=seg,
        max_segments=PACK_MAX_SEGMENTS, with_stats=True,
    )
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(whole), atol=F32_TOL)
    # the groups' statistics are summed: every real token's choice, once
    tokens = sum(len(t.split()) + 2 for t in TEXTS)
    assert int(stats["tokens"]) == tokens
    for name in ("expert_tokens", "skipped", "overflow"):
        np.testing.assert_array_equal(stats[name], whole_stats[name])
    routed = stats["expert_tokens"].sum(axis=1) + stats["skipped"]
    np.testing.assert_array_equal(routed, [tokens] * 3)
    assert int(stats["overflow"].sum()) == 0 and int(stats["multi_pair_tokens"].sum()) == 0


def _with_layers(params, change):
    return dict(params, layers=[change(dict(layer)) for layer in params["layers"]])


def _identity_conv0(layer):
    w = jnp.zeros_like(layer["conv0_w"]).at[-1].set(1.0)
    return dict(layer, conv0_w=w, conv0_b=jnp.zeros_like(layer["conv0_b"]))


def _identity_conv1(layer):
    n, rows, hd = layer["conv1_w"].shape
    w = jnp.zeros_like(layer["conv1_w"]).at[:, rows - hd:].set(jnp.eye(hd))
    return dict(layer, conv1_w=w, conv1_b=jnp.zeros_like(layer["conv1_b"]))


def _never_skip(layer):
    return dict(layer, router_bias=layer["router_bias"].at[-1].set(-10.0))


# a mechanism left out of the program: by its parameters' neutral values
# where it has them, by the function it goes through where it has none
_LEFT_OUT = {
    "temperature": lambda p: _with_layers(p, lambda l: dict(l, tau=jnp.ones_like(l["tau"]))),
    "merge scales": lambda p: _with_layers(p, lambda l: dict(l, alpha=jnp.ones_like(l["alpha"]))),
    "carried router state": lambda p: _with_layers(p, lambda l: dict(l, gamma=jnp.zeros(()))),
    "selection bias": lambda p: _with_layers(
        p, lambda l: dict(l, router_bias=jnp.zeros_like(l["router_bias"]))
    ),
    "skip choice": lambda p: _with_layers(p, _never_skip),
    "depthwise convolution": lambda p: _with_layers(p, _identity_conv0),
    "grouped convolution": lambda p: _with_layers(p, _identity_conv1),
}


def _plain_shift(x, seg, n):
    """`own_past` blind to the seam: the slot n before, whosever it is."""
    if n == 0:
        return x
    return jnp.pad(x, [(0, 0), (n, 0)] + [(0, 0)] * (x.ndim - 2))[:, :-n]


_OWN_PAST = zaya.own_past


def _no_value_shift(x, seg, n):
    # the shifted value heads are the only 3-d [.., kv/2 x 128] operand
    if x.ndim == 3 and x.shape[-1] == 128 and n == 1:
        return x
    return _OWN_PAST(x, seg, n)


_PATCHED = {
    "seam cut": ("own_past", _plain_shift),
    "value shift": ("own_past", _no_value_shift),
    "q-k means": ("group_means", lambda q, k: (jnp.zeros_like(q), jnp.zeros_like(k))),
    "partial rope": ("rotate", lambda x, cos, sin, scale=1.0: x),
}


def _every_row_is_own(seg, n):
    """`cca_latent.own_row` blind to the seam (the block's first rows still
    have no past: the roll would hand them the row's last slots)."""
    return jax.lax.broadcasted_iota(jnp.int32, seg.shape, 0) >= n


# the same four, left out of the kernel: by the function of
# `ops/kernels/cca_latent.py` that each goes through
_PATCHED_IN_THE_KERNEL = {
    "seam cut": ("own_row", _every_row_is_own),
    "value shift": ("_shifted", lambda v, own, shift: v),
    "q-k means": ("_means", lambda q, k: ([jnp.zeros_like(a) for a in q], jnp.zeros_like(k))),
    "partial rope": ("_turned", lambda x, cos, sin: x),
}


@pytest.fixture
def fresh_kernel_traces():
    """A patched kernel is another function: `kernel_call` keeps none of an
    earlier test's traces, and hands none of this one's on."""
    kernels._jitted.cache_clear()
    yield
    kernels._jitted.cache_clear()


@pytest.mark.parametrize("use_flash", [False, True], ids=["dense", "kernel-interpreted"])
@pytest.mark.parametrize("what", sorted(_LEFT_OUT) + sorted(_PATCHED))
def test_leaving_a_mechanism_out_fails_the_comparison(what, use_flash, monkeypatch,
                                                      fresh_kernel_traces):
    """Every mechanism of the layer is drawn away from its neutral value,
    so a program without it is another model: each left out of the program
    moves the vectors by ten tolerances or more, and the untouched program
    agrees.  On both paths: the dense definition, and the fused one, where
    the latent's mechanisms run (and are left out) inside `cca_latent`,
    interpreted."""
    model = tiny_model()
    enc = program_encoder(model, seed=7)
    want = reference_vectors(model, 7, TEXTS)
    np.testing.assert_allclose(packed_vectors(enc, use_flash=use_flash), want, atol=F32_TOL)
    params = enc.lm.params
    if what in _LEFT_OUT:
        params = _LEFT_OUT[what](params)
    elif use_flash:
        name, stand_in = _PATCHED_IN_THE_KERNEL[what]
        monkeypatch.setattr(latent, name, stand_in)
        kernels._jitted.cache_clear()
    else:
        name, stand_in = _PATCHED[what]
        monkeypatch.setattr(zaya, name, stand_in)
    got = packed_vectors(enc, params=params, use_flash=use_flash)
    assert np.abs(got - want).max() > 10 * F32_TOL, what


def test_the_seam_rule():
    """Row t-n of the own document or zero: across a seam, at a row's
    first slots, over padding, and for operands of any rank."""
    seg = jnp.asarray([[1, 1, 1, 2, 2, 0, 0], [1, 2, 2, 2, 3, 3, 3]], jnp.int32)
    x = jnp.arange(1, 15, dtype=jnp.float32).reshape(2, 7, 1)
    one = np.asarray(zaya.own_past(x, seg, 1))[..., 0]
    np.testing.assert_array_equal(one, [[0, 1, 2, 0, 4, 0, 0], [0, 0, 9, 10, 0, 12, 13]])
    two = np.asarray(zaya.own_past(x, seg, 2))[..., 0]
    np.testing.assert_array_equal(two, [[0, 0, 1, 0, 0, 0, 0], [0, 0, 0, 9, 0, 0, 12]])
    assert zaya.own_past(x, seg, 0) is x
    wide = jnp.broadcast_to(x[..., None], (2, 7, 3, 4))
    np.testing.assert_array_equal(
        np.asarray(zaya.own_past(wide, seg, 1))[..., 2, 3], one
    )


@pytest.mark.parametrize("length,kv_heads,group", [(96, 2, 2), (200, 1, 4), (504, 2, 4)])
def test_the_kernel_agrees_with_its_dense_definition(length, kv_heads, group):
    """Interpreted on the CPU: rows of one to three query blocks, off the
    128-lane tile (the overrun rows zeroed inside the call), packed
    documents and padding."""
    rng = np.random.default_rng(length)
    b, hd = 2, kernel.HEAD_DIM
    q = jnp.asarray(rng.normal(size=(b, length, kv_heads * group * hd)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, length, kv_heads * hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, length, kv_heads * hd)), jnp.float32)
    cuts = sorted(rng.choice(np.arange(1, length - 8), size=3, replace=False))
    seg = np.zeros((b, length), np.int32)
    for row in range(b):
        for s, (lo, hi) in enumerate(zip([0] + cuts, cuts + [length - 5 * row])):
            seg[row, lo:hi] = s + 1
    seg = jnp.asarray(seg)
    assert kernel.supports(length, kv_heads * group, kv_heads, hd)
    got = kernel.cca_attention(q, k, v, seg, interpret=True)
    want = kernel.cca_attention_dense(q, k, v, seg, kv_heads=kv_heads)
    real = np.asarray(seg) > 0
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real], atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()
    assert not kernel.supports(640, 8, 2, 128) and not kernel.supports(504, 8, 2, 64)


def _seam_slab(length: int) -> np.ndarray:
    """Five rows of `length` >= 40 slots, so that every look-back meets a
    seam, a row's start and a padding slot: one document and padding;
    three, the first a single token (so the second begins at slot 1), the
    last to the row's end; nine of 1 to 7 tokens and more, a padding slot
    between two of them; a row of padding; a row that is one document."""
    seg = np.zeros((5, length), np.int32)
    seg[0, : length - 6] = 1
    seg[1, 0], seg[1, 1 : length // 2], seg[1, length // 2 :] = 1, 2, 3
    at = 0
    for s, n in enumerate((3, 1, 2, 7, 1, 1, 5, 2, length - 30)):
        seg[2, at : at + n] = s + 1
        at += n + (s == 3)  # one empty slot after the fourth
    seg[4] = 1
    return seg


def _latent_operands(length: int, kv_heads: int, group: int, taps: int = 2):
    """A layer's five leaves of the latent drawn as `init_params` draws
    them, the projection's output, a slab of seams and its RoPE tables."""
    config = zaya.ZayaConfig(
        heads=kv_heads * group, kv_heads=kv_heads, conv_taps0=taps, conv_taps1=taps,
        max_len=length, dtype="float32", param_dtype="float32",
    )
    n, hd = config.heads + kv_heads, config.head_dim
    rng = np.random.default_rng([length, kv_heads, group])

    def drawn(*shape, mean=0.0, std=1.0):
        return jnp.asarray(mean + std * rng.normal(size=shape), jnp.float32)

    layer = {
        "conv0_w": drawn(taps, n * hd, std=taps ** -0.5),
        "conv0_b": drawn(n * hd, std=zaya.CONV_BIAS_STD),
        "conv1_w": drawn(n, taps * hd, hd, std=(taps * hd) ** -0.5),
        "conv1_b": drawn(n * hd, std=zaya.CONV_BIAS_STD),
        "tau": drawn(kv_heads, mean=zaya.TAU_MEAN, std=zaya.TAU_STD),
    }
    seg = jnp.asarray(_seam_slab(length))
    qkv = drawn(*seg.shape, (n + kv_heads) * hd)
    return config, layer, qkv, seg, rope_tables(packed_positions(seg), config.rope_theta)


@pytest.mark.parametrize("kv_heads,group", [(2, 4), (1, 4), (2, 1)])
@pytest.mark.parametrize("length", [40, 128, 504])
def test_the_latent_kernel_agrees_with_its_dense_definition(length, kv_heads, group):
    """`cca_latent` interpreted on the CPU against `zaya.latent_dense`:
    rows of under one tile, one tile and the ingest slab's 504 (the overrun
    rows never written), both halves of the value heads, rows packed with
    1, 3 and 9 documents and padding (`_seam_slab`)."""
    c, layer, qkv, seg, rope = _latent_operands(length, kv_heads, group)
    assert latent.supports(length, c.heads, kv_heads, c.head_dim, c.rotary_dim, 2, 2)
    got = latent.cca_latent(qkv, seg, rope, layer, heads=c.heads, kv_heads=kv_heads,
                            interpret=True)
    want = zaya.latent_dense(qkv, layer, c, seg, rope)
    real = np.asarray(seg) > 0
    for name, g, w in zip(("q", "k", "v"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_allclose(
            np.asarray(g)[real], np.asarray(w)[real], atol=2e-5, err_msg=name
        )
        assert np.isfinite(np.asarray(g)).all(), name
    # a token of the first half of a document's value heads is its own; of
    # the second half, at a document's first slot, nobody's
    first = np.asarray(seg) != np.pad(np.asarray(seg), ((0, 0), (1, 0)))[:, :-1]
    shifted = np.asarray(got[2])[..., (kv_heads - kv_heads // 2) * 128:]
    assert not shifted[first & real].any()


def test_the_latent_kernel_takes_other_taps_and_refuses_what_it_does_not_tile():
    """Three taps in each convolution look three rows back; the shapes
    `supports` refuses are refused by the call too."""
    c, layer, qkv, seg, rope = _latent_operands(40, 2, 2, taps=3)
    got = latent.cca_latent(qkv, seg, rope, layer, heads=4, kv_heads=2, interpret=True)
    want = zaya.latent_dense(qkv, layer, c, seg, rope)
    real = np.asarray(seg) > 0
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g)[real], np.asarray(w)[real], atol=2e-5)
    assert not latent.supports(640, 8, 2, 128, 64, 2, 2)  # over one tile of rows
    assert not latent.supports(504, 8, 2, 64, 64, 2, 2)  # a head that is no lane tile
    assert not latent.supports(504, 8, 2, 128, 128, 2, 2)  # RoPE's tables are 64 wide
    assert not latent.supports(504, 8, 3, 128, 64, 2, 2)
    assert not latent.supports(504, 8, 2, 128, 64, latent.MAX_TAPS + 1, 2)
    assert not latent.supports(504, 8, 2, 128, 64, 2, 0)
    with pytest.raises(ValueError, match="cca_latent: unsupported shape"):
        latent.cca_latent(qkv, seg, rope, layer, heads=2, kv_heads=2, interpret=True)


@pytest.mark.parametrize("n", [1, 2, 3])
def test_the_kernels_seam_rule_is_own_pasts(n):
    """The two copies of the seam rule on the same `seg`: `zaya.own_past`
    keeps a slot's look-back exactly where `cca_latent.own_row`, run as the
    kernel runs it (a slab row a block, a row's id along its lanes), says
    the row n back is the slot's own document's."""
    from jax.experimental import pallas as pl

    seg = jnp.asarray(_seam_slab(48))
    kept = np.asarray(zaya.own_past(jnp.ones(seg.shape + (1,), jnp.float32), seg, n))[..., 0]

    def body(seg_ref, o_ref):
        o_ref[0] = latent.own_row(seg_ref[0], n).astype(jnp.int32)

    lanes = jnp.broadcast_to(seg[:, :, None], seg.shape + (128,))
    block = pl.BlockSpec((1, seg.shape[1], 128), lambda i: (i, 0, 0))
    own = pl.pallas_call(
        body, grid=(seg.shape[0],), in_specs=[block], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(lanes.shape, jnp.int32), interpret=True,
    )(lanes)
    np.testing.assert_array_equal(np.asarray(own)[..., 0], kept.astype(np.int32))
    np.testing.assert_array_equal(np.asarray(own)[..., 127], kept.astype(np.int32))
    assert kept.sum() > 0 and (kept[:, :n] == 0).all()


def test_one_gate_decides_both_kernels(monkeypatch):
    """On the TPU a slab runs the two kernels or the dense definition of
    both: the gate is false where either kernel's `supports` is."""
    config = zaya.ZayaConfig()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert zaya.packed_attention_fused(config, 504)
    assert zaya.packed_attention_fused(config, 40)  # the read-back's short slab
    assert not zaya.packed_attention_fused(config, 16)
    assert not zaya.packed_attention_fused(config, 504, use_flash=False)
    # the latent's kernel alone refuses: a convolution of more taps than it unrolls
    import dataclasses

    wide = dataclasses.replace(config, conv_taps0=latent.MAX_TAPS + 1)
    assert kernel.supports(504, wide.heads, wide.kv_heads, wide.head_dim)
    assert not zaya.packed_attention_fused(wide, 504)
    # the attention's kernel alone refuses
    with monkeypatch.context() as patch:
        patch.setattr(kernel, "supports", lambda *shape: False)
        assert latent.supports(504, 8, 2, 128, 64, 2, 2)
        assert not zaya.packed_attention_fused(config, 504)
    assert zaya.packed_attention_fused(config, 504)


@pytest.mark.parametrize("fused", [True, False], ids=["attn_fused", "attn_dense"])
def test_a_counted_batch_runs_the_kernels_its_counter_names(fused, monkeypatch,
                                                            fresh_kernel_traces):
    """`launch.encode.attn_fused` / `attn_dense` is the count of how often
    the mechanism engages: the packed program of a batch counted fused
    calls both kernels by name, of one counted dense neither."""
    from pathway_tpu.internals import tracing
    from pathway_tpu.ops.knn import DeviceKnnIndex, FusedEmbedSearch

    monkeypatch.setattr(
        zaya, "packed_attention_fused", lambda config, length, use_flash=None: fused
    )
    tracing.reset_spans()
    enc = program_encoder(tiny_model(), 4)
    search = FusedEmbedSearch(enc, DeviceKnnIndex(enc.dimension, metric="cos", reserved_space=64))
    payload, _ = search.prepare_batch(list(range(6)), [text_of(20 + 7 * i, i) for i in range(6)])
    search.dispatch_batch(payload)
    totals = tracing.spans_status()["totals"]
    counted, other = ("attn_fused", "attn_dense") if fused else ("attn_dense", "attn_fused")
    assert totals[f"launch.encode.{counted}"]["count"] == 1
    assert f"launch.encode.{other}" not in totals
    ids, seg = payload[2:4]
    program = enc.lm._packed_jit.__wrapped__  # the packed program, not jitted
    text = str(jax.make_jaxpr(lambda p, i, s: program(p, i, s, PACK_MAX_SEGMENTS))(
        enc.lm.params, jnp.asarray(ids), jnp.asarray(seg)
    ))
    assert ("name=cca_latent" in text) == fused
    assert ("name=cca_attention" in text) == fused
    assert text.count("pallas_call") == (2 if fused else 0)  # traced once each, called a layer


def test_the_latent_kernel_is_one_function_for_all_the_layers(fresh_kernel_traces):
    """PR 41's property for the new name: 20 layers of the attention
    sublayer call ONE jitted `cca_latent` (and one `cca_attention`), traced
    once and lowered to one function of the program's module."""
    depth = 20
    c, layer, qkv, seg, rope = _latent_operands(40, 2, 2)
    layer = dict(
        layer, ln1=jnp.ones((c.hidden,)), wqkv=jnp.zeros((c.hidden, qkv.shape[2])),
        wo=jnp.zeros((c.heads * c.head_dim, c.hidden)),
    )
    calls = {
        latent.kernel_call("cca_latent", latent._latent, heads=4, kv_heads=2) for _ in range(depth)
    }
    assert len(calls) == 1

    def trunk(x, layers):
        for each in layers:
            x = x + zaya._attention(x, each, c, seg, rope, fused=True)
        return x

    x = jnp.zeros(seg.shape + (c.hidden,), jnp.float32)
    text = jax.jit(trunk).trace(x, [layer] * depth).lower().as_text()
    for name in ("cca_latent", "cca_attention"):
        assert text.count(f"func.func private @{name}(") == 1, name
        assert text.count(f"call @{name}(") == depth, name
    assert kernels._jitted.cache_info().currsize == 2


def test_two_shares_of_the_experts_add_up_to_the_whole_layer():
    """The guide's share test on the shared path: with 8 of 16 experts
    held from 0 and from 8, the two shares' routed parts of a layer add up
    to what the chip that holds all 16 computes, under this trunk's
    routing (top-1 of 17: "skip" is nobody's)."""
    whole = zaya.ZayaConfig(
        vocab_size=64, hidden=64, layers=1, heads=4, expert_mlp_dim=32,
        n_routed_experts=16, router_hidden=32, experts_held=16, max_len=64,
        dtype="float32", param_dtype="float32",
    )
    import dataclasses

    key = jax.random.PRNGKey(3)
    layer = zaya.init_params(key, whole)["layers"][0]
    t = 96
    h = jax.random.normal(jax.random.PRNGKey(4), (t, 64), jnp.float32)
    valid = jnp.arange(t) < 90
    experts, weights, _ = zaya.route(h, jnp.zeros((t, 32)), layer, whole)
    assert 0 < int((experts == 16).sum()) < t  # some skip, not all
    full, counts, over = moe.held_experts(
        h, valid, layer, whole, routing=(experts, weights)
    )
    parts, held = [], []
    for offset in (0, 8):
        share = dataclasses.replace(whole, experts_held=8, expert_offset=offset)
        its = zaya.init_params(key, share)["layers"][0]
        # a share's experts are the uncut model's, by their global index
        np.testing.assert_array_equal(
            its["experts_gate"], layer["experts_gate"][offset:offset + 8]
        )
        y, n, o = moe.held_experts(h, valid, its, share, routing=(experts, weights))
        parts.append(np.asarray(y))
        held.append(np.asarray(n))
        assert int(o) == 0
    np.testing.assert_allclose(parts[0] + parts[1], np.asarray(full), atol=1e-6)
    np.testing.assert_array_equal(np.concatenate(held), counts)
    routed_here = int(counts.sum())
    assert routed_here == int((valid & (experts[:, 0] < 16)).sum()) and int(over) == 0
    assert not np.asarray(full)[90:].any()  # padding routes nothing


def test_the_counters_follow_the_batches():
    """`zaya.*` from the segment lengths on the host and from the device's
    statistics, the shared path's `moe.*` beside them: "skip" is routed and
    not held."""
    from pathway_tpu.internals import tracing

    def totals():
        return {k: v["count"] for k, v in tracing.spans_status()["totals"].items()}

    enc = program_encoder(tiny_model(), seed=5)
    before = totals()
    ids, seg, _ = pack_batch(enc.tokenizer, TEXTS, max_len=256, token_budget=128)
    enc.lm.encode_packed(ids, seg, PACK_MAX_SEGMENTS)
    enc.lm.count_stats()
    after = totals()
    grew = lambda name: after.get(name, 0) - before.get(name, 0)  # noqa: E731
    lengths = np.array([len(t.split()) + 2 for t in TEXTS])
    assert grew("zaya.tokens") == lengths.sum()
    assert grew("zaya.seam_tokens") == len(TEXTS)
    assert grew("zaya.scored_pairs") == (lengths * (lengths + 1) // 2).sum() * 4 * 3
    assert grew("moe.pairs_routed") == lengths.sum() * 3
    assert 0 < grew("zaya.skipped_tokens") < grew("moe.pairs_routed")
    assert grew("moe.pairs_held") == grew("moe.pairs_routed") - grew("zaya.skipped_tokens")
    assert grew("moe.groups_aligned") + grew("moe.groups_packed") == 3
    assert grew("moe.overflow_pairs") == 0 and grew("moe.multi_pair_tokens") == 0
    assert grew("moe.fused_returns") == 0  # top-1: one inverse permutation


def test_served_path_ingests_and_retrieves_with_the_embedder():
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
    assert totals["zaya.tokens"]["count"] == meta["real_tokens"]
    assert totals["moe.pairs_routed"]["count"] > 0


def test_the_module_is_found_by_its_configuration_and_refuses_a_mesh():
    config = zaya.TINY
    module = model_module(config)
    assert module is zaya and module.LM is trunk.PackedTrunkLM
    for name in ("forward", "init_params", "param_sharding_rules",
                 "packed_attention_fused", "tokenizer"):
        assert callable(getattr(module, name))
    assert module.tokenizer(config).vocab_size == config.vocab_size
    with pytest.raises(NotImplementedError, match="stage 0 of two.*hand-over"):
        zaya.param_sharding_rules(config, mesh=object())
    # off the TPU the dense definition runs; the override is the tests'
    assert not zaya.packed_attention_fused(config, 504)
    assert zaya.packed_attention_fused(config, 504, use_flash=True)
    # a slab of the cell's size is one row group; twice that is two
    assert trunk.row_chunks(56, 504, zaya.ROW_TOKENS) == 1
    assert trunk.row_chunks(112, 504, zaya.ROW_TOKENS) == 2
    published = zaya.ZayaConfig()
    assert published.active_flops_per_token(0.0) == pytest.approx(
        2 * 20 * (2048 * 1536 + 1024 * 2048 + 10 * 2 * 128 * 128
                  + 2048 * 256 + 2 * 256 * 256 + 256 * 17 + 3 * 2048 * 2048)
    )


def test_the_experts_way_out_is_drawn_at_the_residual_scale_of_the_published_depth():
    """gate and up at the fan-in scale, down at 1 / sqrt(2 x depth) of it,
    in the program's initialiser and in the reference's alike: a token that
    takes another expert at a near-tie then moves the stream by a few
    hundredths, not by a tenth (the configuration's `init` says why)."""
    from chipbench.architectures.zaya_decoder.reference import make_layer

    model = tiny_model(hidden_size=256, moe_intermediate_size=256)
    enc = program_encoder(model, seed=5)
    assert enc.config.depth == model["num_hidden_layers"] == 40
    layer = enc.lm.params["layers"][1]
    fan_in = model["moe_intermediate_size"]
    want = {"experts_gate": 256 ** -0.5, "experts_up": 256 ** -0.5,
            "experts_down": (2 * 40 * fan_in) ** -0.5}
    made = make_layer(model, 5, 1)
    for name, std in want.items():
        assert float(np.std(np.asarray(layer[name]))) == pytest.approx(std, rel=0.02), name
        np.testing.assert_allclose(np.asarray(made[name]), np.asarray(layer[name]), rtol=1e-6)


def test_the_read_back_path_encodes_as_the_ingest_path_does():
    """`encode_batch` + `forward` unpacked (what the fused search program
    traces) against `pack_batch` + the packed program, same texts."""
    enc = program_encoder(tiny_model(), seed=13)
    texts = TEXTS[:6]
    ids, mask = encode_batch(enc.tokenizer, texts, max_len=256)
    unpacked = np.asarray(zaya.forward(
        enc.lm.params, enc.config, jnp.asarray(ids, jnp.int32), jnp.asarray(mask, jnp.int32)
    ))[: len(texts)]
    np.testing.assert_allclose(unpacked, enc.encode_packed(texts), atol=F32_TOL)
