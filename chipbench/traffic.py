"""The one general traffic generator: documents from a traffic file and a seed.

A traffic mix is a data file (chipbench/traffic/<name>.json) of parameters;
this module turns it into jsonl files.  Two rules keep runs comparable:

  * every seed gets the same multiset of document lengths — the quantiles
    of the mix's length distribution — in another order, and every file of
    a run carries that one order.  The words differ from document to
    document, so every document is unique; the shapes the program has to
    compile are those of the warm-up files, whatever the seed;
  * everything is a function of (traffic file, seed, file index), so a
    document is made again on demand (the comparison needs a sample's
    texts) and nothing is kept in memory.
"""

from __future__ import annotations

import os
import statistics

import numpy as np

_SYLLABLES = [
    c + v for c in "bcdfghjklmnprstvwz" for v in "aeiou"
]  # 90 syllables


def vocabulary(n_words: int) -> np.ndarray:
    """`n_words` distinct lowercase pseudo-words of two to four syllables,
    the same in every run: one word is one token to a tokenizer that splits
    on letters and digits."""
    rng = np.random.default_rng(20260930)
    words: dict = {}
    while len(words) < n_words:
        k = int(rng.integers(2, 5))
        word = "".join(_SYLLABLES[i] for i in rng.integers(0, 90, size=k))
        words.setdefault(word, None)
    return np.array(list(words), dtype=object)


def length_multiset(lengths: dict, n: int) -> np.ndarray:
    """The `n` quantiles ((j + 0.5) / n) of the length distribution, in
    words, clipped to [min, max]; the top ones are pinned to max so that
    every file holds documents of the longest class."""
    q = (np.arange(n) + 0.5) / n
    if lengths["dist"] == "lognormal":
        normal = statistics.NormalDist()
        z = np.array([normal.inv_cdf(float(x)) for x in q])
        raw = np.exp(np.log(lengths["median"]) + lengths["sigma"] * z)
    elif lengths["dist"] == "uniform":
        raw = lengths["min"] + q * (lengths["max"] - lengths["min"])
    else:
        raise ValueError(f"unknown length distribution {lengths['dist']!r}")
    out = np.clip(np.rint(raw), lengths["min"], lengths["max"]).astype(np.int64)
    out[-max(1, int(lengths.get("longest_per_file", 1))):] = lengths["max"]
    return out


class Corpus:
    """The documents of one run: file `i` holds `docs_per_file` documents
    whose lengths are `self.lengths` (one seeded order for all files)."""

    def __init__(self, traffic: dict, seed: int, docs_per_file: int | None = None):
        self.seed = int(seed)
        self.docs_per_file = int(docs_per_file or traffic["docs_per_file"])
        self.vocab = vocabulary(int(traffic["vocabulary_words"]))
        multiset = length_multiset(traffic["length_words"], self.docs_per_file)
        order = np.random.default_rng([self.seed, 0]).permutation(len(multiset))
        self.lengths = multiset[order]
        self.longest = int(multiset.max())
        self._ends = np.cumsum(self.lengths)

    def file_docs(self, index: int) -> list:
        """The texts of file `index`, in file order."""
        rng = np.random.default_rng([self.seed, 1, int(index)])
        picks = rng.integers(0, len(self.vocab), size=int(self._ends[-1]))
        words = self.vocab[picks]
        return [
            " ".join(words[lo:hi])
            for lo, hi in zip(self._ends - self.lengths, self._ends)
        ]

    def write_file(self, index: int, path: str) -> None:
        """One jsonl file, one {"data": text} a line.  The words are plain
        letters, so no escaping is needed."""
        lines = ['{"data": "%s"}\n' % text for text in self.file_docs(index)]
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.writelines(lines)
        os.replace(tmp, path)

    def longest_positions(self) -> np.ndarray:
        """Positions within a file of the documents of the longest class."""
        return np.flatnonzero(self.lengths == self.longest)
