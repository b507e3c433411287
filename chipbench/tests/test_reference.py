"""The plain reference against the program's encoder at a small size on
the CPU, both in float32: the tokenizer rule, the weights from the seed and
the two layer layouts (pre-LN MiniLM, post-LN e5) are the same model."""

import numpy as np
import pytest

from chipbench import reference

TEXTS = [
    "bafe kolu mizo bafe tunari",
    "Zeta zeta, ZETA!  kolu-mizo 42 x",
    " ".join(f"w{i}" for i in range(40)),
]


def test_tokenizer_rule():
    ids = reference.token_ids("Kolu kolu, x9!", 30522, 16)
    assert ids[0] == 1 and ids[-1] == 2 and len(ids) == 7
    assert ids[1] == ids[2]  # lowercased
    assert all(4 <= i < 30522 for i in ids[1:-1])
    assert reference.token_ids("a b c d", 30522, 4)[-1] != 2  # the cut drops [SEP]


def test_tokenizer_matches_the_programs():
    from pathway_tpu.models.tokenizer import HashTokenizer

    tok = HashTokenizer(vocab_size=30522)
    for text in TEXTS:
        assert reference.token_ids(text, 30522, 24) == tok.encode(text, 24)


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
