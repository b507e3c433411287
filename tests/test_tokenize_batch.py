"""`tokenize_batch` (models/tokenizer.py): the native tokenizer
(native/tokenizer.cpp) and `HashTokenizer.encode` give the same ids to the
last one, the path is chosen a text at a time, and everything the native
code cannot read exactly stays on the Python path.  All on the CPU; the
native cases skip where no compiler is found."""

from __future__ import annotations

import threading

import numpy as np
import pytest

from pathway_tpu import native
from pathway_tpu.internals import tracing
from pathway_tpu.models.tokenizer import (
    ByteTokenizer,
    HashTokenizer,
    WordPieceTokenizer,
    encode_batch,
    pack_batch,
    tokenize_batch,
)
from tests.test_device_pipeline import _env, _texts_by_path


@pytest.fixture()
def library():
    if native.load() is None:
        pytest.skip("no native tokenizer: no compiler found")


def _random_ascii(rng, n_texts: int, alphabet, longest: int = 200) -> list:
    alphabet = np.asarray(alphabet, dtype=np.uint8)
    return [
        bytes(rng.choice(alphabet, size=int(rng.integers(0, longest)))).decode("ascii")
        for _ in range(n_texts)
    ]


def _texts(case: str, seed: int) -> list:
    rng = np.random.default_rng([seed, 35])
    if case == "all_code_points":
        return _random_ascii(rng, 64, np.arange(128))
    if case == "control_characters":
        # what `\s` takes and isspace() does not (0x1C-0x1F), \v and \f,
        # and the control characters that are tokens, between words
        return _random_ascii(rng, 64, list(range(0x20)) + [0x7F] + list(b"abAB09 "))
    if case == "upper_case":
        return _random_ascii(rng, 64, list(b"ABCXYZabcxyz019 .,'"))
    if case == "long_words":
        words = [
            "".join(rng.choice(list("abcXYZ019"), size=int(n)))
            for n in (255, 256, 257, 300, 1000, 5000)
        ]
        return words + [" ".join(words), "x " + words[2] + "!" + words[3]]
    if case == "empty":
        return ["", " ", "\t\n\x0b\x0c\r\x1c\x1d\x1e\x1f", "a", "", "."]
    raise AssertionError(case)


def _both_paths(tok, texts, max_len):
    """`tokenize_batch` by the native library and by `tokenizer.encode`."""
    before = _texts_by_path()
    got = tokenize_batch(tok, texts, max_len)
    after = _texts_by_path()
    assert (after[0] - before[0], after[1] - before[1]) == (len(texts), 0)
    with _env(PATHWAY_DISABLE_NATIVE="1"):
        want = tokenize_batch(tok, texts, max_len)
    assert _texts_by_path() == (after[0], after[1] + len(texts))
    return got, want


def _assert_rows_are(tok, texts, max_len, ids, lengths):
    assert ids.dtype == np.int32 and lengths.dtype == np.int32
    assert ids.shape[0] == len(texts) == lengths.shape[0]
    for i, text in enumerate(texts):
        want = tok.encode(text, max_len)
        assert lengths[i] == len(want), (i, text)
        assert ids[i, : lengths[i]].tolist() == list(want), (i, text)
        assert not ids[i, lengths[i]:].any()


@pytest.mark.parametrize("max_len", [512, 24, 2, 1])
@pytest.mark.parametrize(
    "case",
    ["all_code_points", "control_characters", "upper_case", "long_words", "empty"],
)
def test_native_ids_equal_python_ids(library, case, max_len):
    """Seeded random ASCII over all 128 code points, upper case, words over
    256 bytes, the empty text; at a `max_len` that cuts most texts
    `[SEP]` is dropped on both paths."""
    tok = HashTokenizer(30522)
    for seed in range(3):
        texts = _texts(case, seed)
        (ids, lengths), (want_ids, want_lengths) = _both_paths(tok, texts, max_len)
        assert np.array_equal(lengths, want_lengths)
        assert np.array_equal(ids, want_ids)
        _assert_rows_are(tok, texts, max_len, ids, lengths)
        if max_len == 24 and case == "all_code_points":
            cut = lengths == max_len
            assert cut.any() and (ids[cut, -1] != 2).any()  # no [SEP] kept


def test_the_separators_are_whitespace_and_a_long_word_is_lowered(library):
    """The two places where the native code and `HashTokenizer` parted:
    0x1C-0x1F split words and are no tokens; a word over 256 bytes is
    hashed lowercased."""
    tok = HashTokenizer(30522)
    long_word = "AbC" * 100
    texts = ["a\x1cb\x1dc\x1ed\x1fe", "a b c d e", long_word, long_word.lower()]
    ids, lengths = tokenize_batch(tok, texts, 64)
    assert lengths.tolist() == [7, 7, 3, 3]
    assert np.array_equal(ids[0], ids[1]) and np.array_equal(ids[2], ids[3])
    _assert_rows_are(tok, texts, 64, ids, lengths)


def test_encode_batch_is_the_same_on_both_paths(library):
    tok = HashTokenizer(512)
    texts = _texts("all_code_points", 7)
    for max_len in (256, 16):
        ids, mask = encode_batch(tok, texts, max_len=max_len)
        with _env(PATHWAY_DISABLE_NATIVE="1"):
            want_ids, want_mask = encode_batch(tok, texts, max_len=max_len)
        assert ids.dtype == want_ids.dtype == np.int16
        assert np.array_equal(ids, want_ids) and np.array_equal(mask, want_mask)
        for i, text in enumerate(texts):
            want = tok.encode(text, max_len)
            assert ids[i][mask[i] > 0].tolist() == want


def test_one_non_ascii_text_takes_the_python_path_alone(library):
    """A curly quote in one passage of a file sends that passage through
    `tokenizer.encode`, and the result is the all-Python one."""
    tok = HashTokenizer(30522)
    texts = [f"passage {i} of the file, plain" for i in range(9)]
    texts[4] = "it’s a “quoted” passage, café"
    before = _texts_by_path()
    ids, lengths = tokenize_batch(tok, texts, 32)
    after = _texts_by_path()
    assert (after[0] - before[0], after[1] - before[1]) == (8, 1)
    _assert_rows_are(tok, texts, 32, ids, lengths)
    packed = pack_batch(tok, texts, max_len=32, token_budget=64)
    with _env(PATHWAY_DISABLE_NATIVE="1"):
        want = pack_batch(tok, texts, max_len=32, token_budget=64)
    assert np.array_equal(packed[0], want[0]) and np.array_equal(packed[1], want[1])
    assert packed[2] == want[2]


class _SubclassedHash(HashTokenizer):
    def tokenize(self, text):
        return text.split()


@pytest.mark.parametrize(
    "which",
    ["disabled", "word_piece", "cased", "subclass", "bytes", "pairs"],
)
def test_everything_else_keeps_the_python_path(which):
    """`PATHWAY_DISABLE_NATIVE=1`, a `WordPieceTokenizer`, a `HashTokenizer`
    that keeps case or is subclassed, the byte tokenizer and pairs never
    reach the native library."""
    texts = ["Alpha bravo", "charlie Delta echo", ""]
    pairs = None
    env = {}
    if which == "disabled":
        tok, env = HashTokenizer(512), {"PATHWAY_DISABLE_NATIVE": "1"}
    elif which == "word_piece":
        vocab = ["[PAD]", "[CLS]", "[SEP]", "[UNK]", "alpha", "bravo", "char", "##lie"]
        tok = WordPieceTokenizer({t: i for i, t in enumerate(vocab)})
    elif which == "cased":
        tok = HashTokenizer(512, lowercase=False)
    elif which == "subclass":
        tok = _SubclassedHash(512)
    elif which == "bytes":
        tok = ByteTokenizer()
    else:
        tok, pairs = HashTokenizer(512), ["x y", "z", "w"]
    before = _texts_by_path()
    with _env(**env):
        ids, lengths = tokenize_batch(tok, texts, 16, pairs)
    after = _texts_by_path()
    assert (after[0] - before[0], after[1] - before[1]) == (0, 3)
    for i, text in enumerate(texts):
        want = tok.encode(text, 16) if pairs is None else tok.encode_pair(text, pairs[i], 16)
        assert ids[i, : lengths[i]].tolist() == list(want)


def test_two_threads_tokenise_at_once(library):
    """The pipeline's two prep threads call the library at the same time:
    it keeps no state between calls."""
    tok = HashTokenizer(30522)
    batches = [_texts("all_code_points", seed) * 8 for seed in range(4)]
    with _env(PATHWAY_DISABLE_NATIVE="1"):
        want = [tokenize_batch(tok, texts, 128) for texts in batches]
    wrong: list = []

    def work(k: int) -> None:
        for _ in range(20):
            ids, lengths = tokenize_batch(tok, batches[k], 128)
            if not (np.array_equal(ids, want[k][0]) and np.array_equal(lengths, want[k][1])):
                wrong.append(k)

    threads = [threading.Thread(target=work, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert not wrong
    # each thread counted its own texts
    assert tracing.spans_status()["totals"]["prep.tokenize.native_texts"]["count"] >= 4 * 20 * 512


def test_threads_that_ask_for_the_library_at_once_build_it_once(library, tmp_path, monkeypatch):
    """A checkout's first batches reach the two prep threads together: one
    of them builds, all of them get the library (two builds into one
    temporary file left the loser on the Python path for good)."""
    monkeypatch.setenv("PATHWAY_NATIVE_CACHE", str(tmp_path))
    monkeypatch.setattr(native, "_lib", None)
    got: list = []
    go = threading.Barrier(6)

    def ask() -> None:
        go.wait(timeout=30)
        got.append(native.load())

    threads = [threading.Thread(target=ask) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert not any(t.is_alive() for t in threads)
    assert len(got) == 6 and got[0] is not None
    assert all(lib is got[0] for lib in got)
    assert [p.name for p in tmp_path.iterdir()] == [p.name for p in tmp_path.glob("pw_native_*.so")]
    assert len(list(tmp_path.iterdir())) == 1
    assert not native._build_failed
