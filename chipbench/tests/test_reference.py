"""What every architecture's reference shares: the tokenizer rule, the
seed's fold, and the rounding of the controls."""

import numpy as np

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


def test_the_seed_is_folded_below_32_signed_bits():
    assert reference.weight_seed(5) == reference.weight_seed(5 + (2**31 - 1)) == 5
    assert 0 <= reference.weight_seed(2**31 + 77) < 2**31 - 1


def test_rounding_of_the_controls_moves_a_unit_vector_a_little():
    v = np.linspace(-1.0, 1.0, 64)
    v /= np.linalg.norm(v)
    for kind, least, most in (("bf16", 1e-5, 4e-3), ("fp8", 1e-4, 7e-2), ("int8", 1e-4, 4e-3)):
        gap = np.abs(reference.round_vectors(v[None, :], kind)[0] - v).max()
        assert least < gap < most, (kind, gap)
