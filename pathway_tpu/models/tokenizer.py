"""Offline-friendly tokenizer.

The reference loads HuggingFace tokenizers with downloaded vocab files
(xpacks/llm/embedders.py SentenceTransformerEmbedder). This environment has
zero egress, so the default is a deterministic hashing tokenizer (stable
token ids via blake2, like feature hashing); a wordpiece vocab file is used
when present. Either way the contract is the same: `encode_batch` returns
fixed-shape (ids, mask) arrays bucketed to power-of-two lengths so XLA sees
a small set of shapes.
"""

from __future__ import annotations

import dataclasses
import functools
import re
import zlib
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from pathway_tpu import native as _native
from pathway_tpu.internals import config as _config
from pathway_tpu.internals import tracing
from pathway_tpu.internals.tracing import span

_WORD_RE = re.compile(r"[A-Za-z0-9]+|[^\sA-Za-z0-9]")

PAD_ID = 0
CLS_ID = 1
SEP_ID = 2
UNK_ID = 3
_RESERVED = 4


class HashTokenizer:
    """One hashed id a word.  `shapes`: the slab shapes the model that
    reads the ids takes, where they are not the encoders' (`SlabShapes`;
    its module hands the tokenizer out).  An argument and no subclass:
    `tokenize_batch` reads a batch natively under exactly this class."""

    def __init__(self, vocab_size: int = 30522, lowercase: bool = True,
                 shapes: "SlabShapes | None" = None):
        self.vocab_size = vocab_size
        self.lowercase = lowercase
        self.shapes = shapes or SlabShapes()

    def token_id(self, token: str) -> int:
        # crc32 runs in C and is stable across processes; collisions at
        # 30k-vocab scale are acceptable for a feature-hashing tokenizer
        value = zlib.crc32(token.encode())
        return _RESERVED + value % (self.vocab_size - _RESERVED)

    def tokenize(self, text: str) -> List[str]:
        if self.lowercase:
            text = text.lower()
        return _WORD_RE.findall(text)

    def encode(self, text: str, max_len: int | None = None) -> List[int]:
        ids = [CLS_ID] + [self.token_id(t) for t in self.tokenize(text)] + [SEP_ID]
        if max_len is not None:
            ids = ids[:max_len]
        return ids

    def encode_pair(self, a: str, b: str, max_len: int | None = None) -> List[int]:
        ids = (
            [CLS_ID]
            + [self.token_id(t) for t in self.tokenize(a)]
            + [SEP_ID]
            + [self.token_id(t) for t in self.tokenize(b)]
            + [SEP_ID]
        )
        if max_len is not None:
            ids = ids[:max_len]
        return ids

    def decode(self, ids: Sequence[int]) -> str:
        # hashing is one-way; decode renders placeholder tokens (used only
        # by the random-weight chat model in offline tests)
        return " ".join(f"tok{i}" for i in ids if i >= _RESERVED)

    def count_tokens(self, text: str) -> int:
        return len(self.tokenize(text))


class WordPieceTokenizer:
    """Real WordPiece over a vocab file (reference: the HF tokenizer the
    reference loads for SentenceTransformer models, embedders.py:342).

    Greedy longest-match-first with `##` continuation pieces — the BERT
    algorithm — so ids match HuggingFace's BertTokenizer for ASCII text.
    Special ids come from the vocab ([PAD]/[CLS]/[SEP]/[UNK])."""

    def __init__(self, vocab, lowercase: bool = True):
        if isinstance(vocab, (str, bytes)):
            with open(vocab, encoding="utf-8") as f:
                tokens = [line.rstrip("\n") for line in f]
            vocab = {tok: i for i, tok in enumerate(tokens) if tok}
        self.vocab: dict = dict(vocab)
        self.lowercase = lowercase
        self.vocab_size = max(self.vocab.values()) + 1 if self.vocab else 0
        self.pad_id = self.vocab.get("[PAD]", 0)
        self.cls_id = self.vocab.get("[CLS]", 1)
        self.sep_id = self.vocab.get("[SEP]", 2)
        self.unk_id = self.vocab.get("[UNK]", 3)
        self._inv: dict | None = None

    def tokenize(self, text: str) -> List[str]:
        if self.lowercase:
            text = text.lower()
        pieces: List[str] = []
        for word in _WORD_RE.findall(text):
            pieces.extend(self._wordpiece(word))
        return pieces

    def _wordpiece(self, word: str, max_chars: int = 100) -> List[str]:
        if len(word) > max_chars:
            return ["[UNK]"]
        out: List[str] = []
        start = 0
        while start < len(word):
            end = len(word)
            piece = None
            while start < end:
                sub = word[start:end]
                if start > 0:
                    sub = "##" + sub
                if sub in self.vocab:
                    piece = sub
                    break
                end -= 1
            if piece is None:
                return ["[UNK]"]
            out.append(piece)
            start = end
        return out

    def token_id(self, token: str) -> int:
        return self.vocab.get(token, self.unk_id)

    def encode(self, text: str, max_len: int | None = None) -> List[int]:
        ids = (
            [self.cls_id]
            + [self.token_id(t) for t in self.tokenize(text)]
            + [self.sep_id]
        )
        if max_len is not None and len(ids) > max_len:
            # HF truncation keeps [SEP] as the final token
            ids = ids[: max_len - 1] + [self.sep_id]
        return ids

    def encode_pair(self, a: str, b: str, max_len: int | None = None) -> List[int]:
        ids = (
            [self.cls_id]
            + [self.token_id(t) for t in self.tokenize(a)]
            + [self.sep_id]
            + [self.token_id(t) for t in self.tokenize(b)]
            + [self.sep_id]
        )
        if max_len is not None and len(ids) > max_len:
            ids = ids[: max_len - 1] + [self.sep_id]
        return ids

    def decode(self, ids: Sequence[int]) -> str:
        if self._inv is None:
            self._inv = {i: t for t, i in self.vocab.items()}
        specials = {self.pad_id, self.cls_id, self.sep_id}
        words: List[str] = []
        for i in ids:
            if i in specials:
                continue
            tok = self._inv.get(int(i), "[UNK]")
            if tok.startswith("##") and words:
                words[-1] += tok[2:]
            else:
                words.append(tok)
        return " ".join(words)

    def count_tokens(self, text: str) -> int:
        return len(self.tokenize(text))


class ByteTokenizer:
    """A text as its UTF-8 bytes: `<bos>`, then `BYTE_OFFSET` + byte, cut to
    `max_len` (byte-level models: 64 special ids, then the 256 bytes).  No
    vocabulary to ship, no unknown word; a word of prose is five to eight
    tokens.  `shapes`: the slab shapes the model that reads the ids takes
    (its module hands the tokenizer out, `model_module(config).tokenizer`)."""

    BOS_ID = 1
    BYTE_OFFSET = 64
    lowercase = False

    def __init__(self, vocab_size: int = 320, shapes: "SlabShapes | None" = None):
        self.vocab_size = vocab_size
        self.pad_id = PAD_ID
        self.shapes = shapes or SlabShapes()

    def encode(self, text: str, max_len: int | None = None) -> np.ndarray:
        """[<bos>, 64 + b0, 64 + b1, ...] as an int32 array (thousands of
        ids a page: no list of Python ints)."""
        raw = np.frombuffer(text.encode("utf-8"), dtype=np.uint8)
        if max_len is not None:
            raw = raw[: max(max_len - 1, 0)]
        ids = np.empty(len(raw) + 1, dtype=np.int32)
        ids[0] = self.BOS_ID
        ids[1:] = raw.astype(np.int32) + self.BYTE_OFFSET
        return ids[:max_len]

    def decode(self, ids: Sequence[int]) -> str:
        raw = bytes(int(i) - self.BYTE_OFFSET for i in ids if int(i) >= self.BYTE_OFFSET)
        return raw.decode("utf-8", errors="replace")

    def count_tokens(self, text: str) -> int:
        return len(text.encode("utf-8"))


def bucket_length(n: int, minimum: int = 16, maximum: int = 512) -> int:
    """Power-of-two buckets — the BATCH-dimension policy. Mesh sharding
    depends on it (power-of-two batches divide any power-of-two dp axis,
    minilm.py encode), and it bounds the compile cache to ~log2 shapes."""
    b = minimum
    while b < n and b < maximum:
        b *= 2
    return min(b, maximum)


def seq_bucket_length(n: int, minimum: int = 16, maximum: int = 512) -> int:
    """SEQUENCE-dimension buckets: powers of two up to 32, then multiples
    of 8. The finer high-end granularity matters on the MXU — bulk
    corpora sit just past a power of two (e.g. 51 tokens), and padding
    51 -> 64 instead of 51 -> 56 burns 14% of the FLOPs on pad tokens.
    The sequence axis is never mesh-sharded by the encoder, so the
    power-of-two divisibility constraint of `bucket_length` does not
    apply; shape count stays bounded by maximum/8."""
    if n <= minimum:
        return min(minimum, maximum)
    b = minimum
    while b < n and b < 32:
        b *= 2
    if b >= n:
        return min(b, maximum)
    return min(-(-n // 8) * 8, maximum)


def _slab_length(lengths: Sequence[int], budget: int, max_len: int) -> int:
    """The fixed budget, raised to the sequence bucket of the longest
    document only when one overflows it."""
    longest = max(lengths, default=1)
    if longest <= budget:
        return budget
    return seq_bucket_length(longest, maximum=max(max_len, longest))


@dataclasses.dataclass(frozen=True)
class SlabShapes:
    """The shapes a model's programs are compiled for, asked by
    `encode_batch` and `pack_batch`: `seq_bucket(n, maximum=)` a row's
    length for a longest text of n tokens, `row_bucket(rows)` a packed
    slab's rows, `slab_length(lengths, budget, max_len)` the row length of
    a packed batch.  The defaults are the word-token encoders' (rows of at
    most 512 slots); a model whose kernel takes other tiles says so on the
    tokenizer its module hands out (`tokenizer.shapes`, models/eva.py)."""

    seq_bucket: Callable[..., int] = seq_bucket_length
    row_bucket: Callable[[int], int] = functools.partial(
        seq_bucket_length, minimum=8, maximum=1 << 16
    )
    slab_length: Callable[..., int] = _slab_length


_DEFAULT_SHAPES = SlabShapes()


def tokenize_batch(
    tokenizer,
    texts: Sequence[str],
    max_len: int,
    pair_texts: Sequence[str] | None = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """The batch's token ids as arrays, for `encode_batch` and
    `pack_batch`: (ids [n, longest] int32, lengths [n] int32), text i's ids
    in `ids[i, :lengths[i]]` and zeros after them, each cut to `max_len`.

    The path is chosen a text at a time, by what can be seen of it: an
    ASCII text under a lowercasing `HashTokenizer` is read by the native
    tokenizer (native/tokenizer.cpp: the same ids to the last one, and the
    whole batch in one call that holds no interpreter lock, which the
    engine's tick needs); every other text, every other tokenizer, a pair,
    and every text where the library could not be built or
    `PATHWAY_DISABLE_NATIVE` is set, goes through `tokenizer.encode` and is
    laid into the same arrays.  One curly quote sends one text down the
    Python path, not its file.  Counters `prep.tokenize.native_texts` /
    `.python_texts` (/status "spans") say how often each engaged."""
    n = len(texts)
    with span("prep.tokenize", rows=n):
        native_ok = (
            pair_texts is None
            and type(tokenizer) is HashTokenizer
            and tokenizer.lowercase
            and tokenizer.vocab_size > _RESERVED  # the library divides by the rest
            and _native.load() is not None
        )
        native_rows: List[int] = []
        python_rows: List[int] = []
        for i, text in enumerate(texts):
            (native_rows if native_ok and text.isascii() else python_rows).append(i)
        if pair_texts is None:
            encoded = [tokenizer.encode(texts[i], max_len) for i in python_rows]
        else:
            encoded = [
                tokenizer.encode_pair(texts[i], pair_texts[i], max_len)
                for i in python_rows
            ]
        # a word is a byte at least, and [CLS] and [SEP] two more
        native_texts = [texts[i] for i in native_rows]
        width = min(max_len, 2 + max(map(len, native_texts), default=0))
        width = max(width, max(map(len, encoded), default=0), 1)
        ids = np.zeros((n, width), dtype=np.int32)
        lengths = np.zeros(n, dtype=np.int32)
        if native_rows:
            _native.tokenize_batch_native(
                native_texts, native_rows, tokenizer.vocab_size, ids, lengths
            )
        for i, e in zip(python_rows, encoded):
            ids[i, : len(e)] = e
            lengths[i] = len(e)
        tracing.add("prep.tokenize.native_texts", n=len(native_rows))
        tracing.add("prep.tokenize.python_texts", n=len(python_rows))
    # as wide as the longest text came out, whichever path read it
    return ids[:, : int(lengths.max(initial=1))], lengths


def encode_batch(
    tokenizer: HashTokenizer,
    texts: Sequence[str],
    *,
    max_len: int = 512,
    pair_texts: Sequence[str] | None = None,
    batch_bucket: bool = True,
) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (ids [B', L'], mask [B', L']) padded to bucketed shapes; the
    first len(texts) rows are the real batch."""
    # two spans a batch (never one a text): `tokenize_batch`, and the
    # slab fill after it
    tokens, lengths = tokenize_batch(tokenizer, texts, max_len, pair_texts)
    with span("prep.pack", rows=len(texts)):
        batch = len(texts)
        longest = int(lengths.max()) if batch else 1
        shapes = getattr(tokenizer, "shapes", _DEFAULT_SHAPES)
        seq_len = shapes.seq_bucket(longest, maximum=max_len)
        padded_batch = bucket_length(max(batch, 1), minimum=8, maximum=1 << 16) if batch_bucket else batch
        pad_id = getattr(tokenizer, "pad_id", PAD_ID)
        dtype = _wire_dtype(tokenizer)
        ids = np.full((padded_batch, seq_len), pad_id, dtype=dtype)
        mask = np.zeros((padded_batch, seq_len), dtype=dtype)
        real = np.arange(seq_len) < lengths[:, None]
        mask[:batch] = real
        width = min(tokens.shape[1], seq_len)
        ids[:batch, :width] = np.where(real[:, :width], tokens[:, :width], pad_id)
    return ids, mask


PACK_MAX_SEGMENTS = 32
# a batch whose tokens fill at most this many slab rows is packed longest
# document first (pack_batch): a bucket step of 8 rows is then a sixteenth
# of the slab or more, and its shape may not hang on the arrival order
PACK_SORT_ROWS = 128


def pack_token_budget(default: int = 256) -> int:
    """Slab length for packed ragged batching (PATHWAY_PACK_TOKEN_BUDGET,
    read per call like PATHWAY_INGEST_CHUNK). 0 disables packing and the
    ingest path falls back to the classic one-doc-per-row bucketed
    encode."""
    budget = _config.env("PATHWAY_PACK_TOKEN_BUDGET")
    return default if budget is None else max(0, budget)


def _first_fit(lengths: np.ndarray, order: np.ndarray, slab: int, max_segments: int):
    """`pack_batch`'s placement: the documents, taken in `order`, go each
    to the first row with room for them (of `slab` slots and fewer than
    `max_segments` documents), or open a new one.  Returns the row, the
    segment there and the first slot of every document as int32 arrays.
    In the library where it is loaded (2,048 passages over 600 rows are
    half a million steps of this loop), here otherwise: one rule."""
    if _native.load() is not None:
        return _native.first_fit_native(lengths, order, slab, max_segments)
    needs = lengths.tolist()
    placed = np.zeros((3, len(needs)), dtype=np.int32)
    used: List[int] = []
    held: List[int] = []
    for d in order.tolist():
        need = needs[d]
        row = -1
        for r in range(len(used)):
            if used[r] + need <= slab and held[r] < max_segments:
                row = r
                break
        if row < 0:
            used.append(0)
            held.append(0)
            row = len(used) - 1
        placed[:, d] = row, held[row], used[row]
        held[row] += 1
        used[row] += need
    return tuple(placed)


def pack_batch(
    tokenizer,
    texts: Sequence[str],
    *,
    max_len: int = 512,
    token_budget: int = 256,
    max_segments: int = PACK_MAX_SEGMENTS,
    row_bucket: bool = True,
) -> Tuple[np.ndarray, np.ndarray, List[Tuple[int, int]]]:
    """Packed ragged batching: concatenate variable-length docs into
    fixed token-budget slabs with a segment-ids mask instead of padding
    each doc to the bucket max, so the MXU runs on real tokens.

    Returns (ids [R, L], seg [R, L], slots). seg holds 1..max_segments
    per document within a row (0 = padding); slots[d] = (row, seg - 1)
    locates document d's pooled vector in the encoder's [R, S, H] output.

    Packing is greedy first-fit in arrival order: deterministic, and the
    XLA shape set stays tiny because L is the fixed budget (raised to the
    sequence bucket of the longest doc only when one overflows it) and
    the row count buckets like a sequence axis — packed rows are never
    mesh-sharded, so the power-of-two batch contract does not apply.

    A small batch (its tokens fill at most PACK_SORT_ROWS rows) is packed
    longest document first instead (ties in arrival order), so that the
    rows it takes depend on its documents' lengths and not on their
    order: in arrival order 64 chunks of 202-502 tokens took 55, 56 or 57
    rows by the order they came in, and the 57 a [64, 504] slab where the
    others ran [56, 504] (one seed of 8: 88.3 against 100.1 docs/s, chip
    runs, PR 30); longest first they take 54.  Large batches keep the
    arrival order: a bucket step is 2% of their slab, and their shapes are
    the ones deployments have compiled (sorted, e5-large's [440, 504] slab
    became [432, 504], where XLA's fusion of the out-projection and the
    MLP takes 38.8 ms a layer against 25.4: chip runs, PR 30).
    """
    tokens, lengths = tokenize_batch(tokenizer, texts, max_len)
    with span("prep.pack", rows=len(texts)):
        shapes = getattr(tokenizer, "shapes", _DEFAULT_SHAPES)
        needs = lengths.tolist()
        slab = shapes.slab_length(needs or [1], max(1, int(token_budget)), max_len)
        if sum(needs) <= PACK_SORT_ROWS * slab:
            order = np.argsort(-lengths, kind="stable")
        else:
            order = np.arange(len(needs))
        row_of, seg_of, at_of = _first_fit(lengths, order, slab, max_segments)
        slots = list(zip(row_of.tolist(), seg_of.tolist()))
        n_rows = int(row_of.max(initial=0)) + 1
        padded_rows = shapes.row_bucket(n_rows) if row_bucket else n_rows
        pad_id = getattr(tokenizer, "pad_id", PAD_ID)
        dtype = _wire_dtype(tokenizer)
        ids = np.full((padded_rows, slab), pad_id, dtype=dtype)
        seg = np.zeros((padded_rows, slab), dtype=dtype)
        # one scatter: token j of the batch (documents in arrival order)
        # goes to flat slot row * slab + at + (its place in its document)
        real = np.arange(tokens.shape[1]) < lengths[:, None]
        first = np.cumsum(lengths, dtype=np.int64) - lengths
        start = row_of.astype(np.int64) * slab + at_of
        dest = np.repeat(start - first, lengths) + np.arange(sum(needs))
        np.put(ids, dest, tokens[real])
        np.put(seg, dest, np.repeat(seg_of + 1, lengths))
    return ids, seg, slots


def predict_pad_waste(
    lengths: Sequence[int], batch_size: int, *, max_len: int = 512
) -> float:
    """Predicted padding-waste fraction of the CLASSIC (unpacked) encode
    path for a UDF batch of `batch_size` docs drawn from the sampled
    token `lengths`: real tokens vs the bucketed [B', L'] slab that
    encode_batch would dispatch. Used by the PWT401 analyzer lint to flag
    embedder configs whose batch/bucket shape burns most of the MXU on
    pad tokens."""
    if not lengths or batch_size <= 0:
        return 0.0
    batch = [
        max(1, min(int(lengths[i % len(lengths)]), max_len))
        for i in range(batch_size)
    ]
    seq_len = seq_bucket_length(max(batch), maximum=max_len)
    padded_batch = bucket_length(batch_size, minimum=8, maximum=1 << 16)
    real = sum(batch)
    total = padded_batch * seq_len
    return 1.0 - (real / float(total)) if total else 0.0


def _wire_dtype(tokenizer):
    """THE wire-narrowing policy for token uploads (single source — the
    models upcast on device): int16/uint16 halves the host->device
    transfer of every token batch, the ingest path's largest upload;
    XLA gathers cast indices anyway. Falls back to int32 for
    vocabularies beyond 16-bit range. Masks share the ids dtype (narrow
    on the wire, and safe for in-jit integer sums at any seq length,
    which int8 would not be)."""
    nvocab = getattr(tokenizer, "vocab_size", None)
    if nvocab is None:
        nvocab = len(getattr(tokenizer, "vocab", ())) or (1 << 31)
    if nvocab < (1 << 15):
        return np.int16
    if nvocab < (1 << 16):
        return np.uint16
    return np.int32


class FastTokenizer:
    """Adapter over HuggingFace `tokenizers` (tokenizer.json — the format
    Llama/Mistral checkpoints ship). Same interface as HashTokenizer /
    WordPieceTokenizer, so encode_batch and the models consume it
    unchanged."""

    def __init__(self, path: str):
        from tokenizers import Tokenizer  # type: ignore

        self._tok = Tokenizer.from_file(path)
        self.vocab_size = self._tok.get_vocab_size()
        self.lowercase = False
        self.pad_id = 0
        for cand in ("<pad>", "[PAD]", "<unk>", "<s>"):
            tid = self._tok.token_to_id(cand)
            if tid is not None:
                self.pad_id = tid
                break

    def tokenize(self, text: str) -> List[str]:
        return self._tok.encode(text).tokens

    def encode(self, text: str, max_len: int | None = None) -> List[int]:
        ids = self._tok.encode(text).ids
        if max_len is not None:
            ids = ids[:max_len]
        return ids

    def encode_pair(self, a: str, b: str, max_len: int | None = None) -> List[int]:
        ids = self._tok.encode(a, b).ids
        if max_len is not None:
            ids = ids[:max_len]
        return ids

    def decode(self, ids: Sequence[int]) -> str:
        return self._tok.decode([int(i) for i in ids], skip_special_tokens=True)

    def count_tokens(self, text: str) -> int:
        return len(self._tok.encode(text).ids)
