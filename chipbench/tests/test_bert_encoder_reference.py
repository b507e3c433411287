"""The BERT-style encoder's plain reference: against the program's encoder
at a small size on the CPU, both in float32 (the weights from the seed and
the two layer layouts, pre-LN MiniLM and post-LN e5, are the same model),
and against the embeddings it gave before it became the architecture's own
file (PR 29), to the last bit."""

import hashlib
import json
import os

import numpy as np
import pytest

from chipbench import spec
from chipbench.architectures.bert_encoder import reference

TEXTS = [
    "bafe kolu mizo bafe tunari",
    "Zeta zeta, ZETA!  kolu-mizo 42 x",
    " ".join(f"w{i}" for i in range(40)),
]


def test_weights_come_from_the_seed_alone():
    model = {"hidden": 32, "mlp_dim": 64, "vocab_size": 500, "layers": 2, "heads": 4,
             "max_position_embeddings": 64, "norm_style": "pre"}
    a = reference.make_params(model, 5)
    b = reference.make_params(model, 5)
    c = reference.make_params(model, 5 + (2**31 - 1))  # folded onto the same key
    d = reference.make_params(model, 6)
    assert np.array_equal(a["layers"][1]["up"], b["layers"][1]["up"])
    assert np.array_equal(a["embed"], c["embed"])
    assert not np.array_equal(a["embed"], d["embed"])
    assert abs(float(np.std(a["embed"])) - 0.02) < 0.002


@pytest.mark.parametrize("norm_style", ["pre", "post"])
def test_reference_agrees_with_the_programs_encoder(norm_style):
    from pathway_tpu.models.minilm import SentenceEncoder
    from pathway_tpu.models.transformer import TransformerConfig

    model = {"hidden": 64, "mlp_dim": 128, "vocab_size": 30522, "layers": 2,
             "heads": 4, "max_position_embeddings": 64, "norm_style": norm_style}
    config = TransformerConfig(
        vocab_size=30522, hidden=64, layers=2, heads=4, mlp_dim=128, max_len=64,
        dtype="float32", norm_style=norm_style,
    )
    program = SentenceEncoder("test-model", config=config, seed=11, max_len=48)
    ours = reference.Encoder(model, 11, max_len=48, block=4).embed(TEXTS)
    theirs = np.asarray(program.encode(TEXTS), dtype=np.float64)
    assert np.abs(ours - theirs).max() < 2e-5
    assert np.allclose(np.linalg.norm(ours, axis=1), 1.0)
    # and the lower-precision control is a different computation
    low = reference.Encoder(model, 11, max_len=48, block=4).embed(
        TEXTS, lower_precision="fp8")
    assert np.abs(low - ours).max() > 2e-4


# -- pinned at d241e38, the commit before the reference moved here: two layers at
# published widths, float32 on the CPU, 8 seeded texts of 5-59 words, blocks of 32.
# sha256 of the float32 bytes of the [8, hidden] block (the float64 the reference
# returns holds float32 values), its first and its last four numbers, and the
# digest of the fp8 control's block.
PINNED = {
    ("minilm-l6-docstore", 7): (
        "4d0cb04efa6069fa6382c2ff081be7815737beea3f2069ebf1a0561329e6ceb9",
        [-0.07397759705781937, 0.0471908301115036, -0.10694563388824463, 0.015867428854107857],
        [0.06663654744625092, -0.05398757755756378, 0.052860092371702194, -0.03101436421275139],
        "f4f05b86735872e83e9d46ae80f74f44562424183702e930d2848da9a2d49c62",
    ),
    ("minilm-l6-docstore", 2**31 + 77): (
        "9462e4929647217c31b7217d3fddd55cd6276cde180b952e98f88d044cbb9434",
        [-0.019670499488711357, -0.012711449526250362, 0.07358945906162262, 0.024897629395127296],
        [0.06638588756322861, -0.031119246035814285, -0.01669212244451046, -0.020972324535250664],
        "712397a2620e2277c61a58addfbd6cb66700bdb996eb939be464caf6bc7ec709",
    ),
    ("e5-large-docstore", 7): (
        "a2b43328867d3722ba5bddd53006d68c0ddca2d29b9ab78f6a2bb95842ea67eb",
        [0.022934047505259514, -0.01011058408766985, 0.039271607995033264, -0.04327695071697235],
        [-0.06403126567602158, -0.03393523767590523, 0.03937142714858055, -0.06094159930944443],
        "d9ae632d08482f41504956583d998919974fb5f6d0a1adecc5a5f242b1d81390",
    ),
    ("e5-large-docstore", 2**31 + 77): (
        "55d9fb3e196214db2e3974d3c07dcb555aa4f46cece68e32cd68022420153ed6",
        [-0.010292578488588333, 0.023239487782120705, 0.05543198809027672, -0.004636059515178204],
        [0.0017823238158598542, -0.06425739079713821, -0.04454253986477852, -0.036897819489240646],
        "1d0498c0b9a511b8294ff8575ec703db73d14be321a3cc6642a8338d676314ea",
    ),
}


def _seeded_texts(seed):
    rng = np.random.default_rng(seed)
    return [" ".join(f"w{int(i)}" for i in rng.integers(0, 5000, size=int(n)))
            for n in rng.integers(5, 60, size=8)]


def _digest(vectors):
    as_f32 = vectors.astype(np.float32)
    assert np.array_equal(as_f32.astype(np.float64), vectors)
    return hashlib.sha256(as_f32.tobytes()).hexdigest()


@pytest.mark.parametrize("config,seed", sorted(PINNED))
def test_embeddings_are_bit_equal_to_those_before_the_move(config, seed):
    with open(os.path.join(spec.HERE, "configs", config + ".json")) as f:
        file = json.load(f)
    arch = spec.architecture(file)
    model = arch.costs.dry_cut(file["model"])  # two layers, published widths
    encoder = arch.reference.Encoder(model, seed, max_len=file["store"]["max_len"])
    digest, head, tail, digest_fp8 = PINNED[config, seed]
    vectors = encoder.embed(_seeded_texts(seed))
    assert list(vectors[0, :4]) == head and list(vectors[7, -4:]) == tail
    assert _digest(vectors) == digest
    low = encoder.embed(_seeded_texts(seed), lower_precision="fp8")
    assert _digest(low) == digest_fp8
