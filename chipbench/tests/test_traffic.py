"""The generator: reproducible from the seed, the same sizes for every
seed in another order, every document unique."""

import json
import os

import numpy as np

from chipbench import spec, traffic


def _mix(name):
    with open(os.path.join(spec.HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def test_same_seed_same_documents_other_seed_other_order():
    mix = _mix("ingest-passages")
    a, b = traffic.Corpus(mix, 7), traffic.Corpus(mix, 7)
    c = traffic.Corpus(mix, 2**31 + 12345)  # the driver's seeds are large
    assert a.file_docs(3) == b.file_docs(3)
    assert a.file_docs(3) != a.file_docs(4)
    assert sorted(a.lengths) == sorted(c.lengths)
    assert list(a.lengths) != list(c.lengths)
    assert a.file_docs(0) != c.file_docs(0)


def test_lengths_follow_the_mix_and_every_file_has_the_longest_class():
    for name, lo, hi in (("ingest-passages", 16, 254), ("ingest-chunks", 200, 500)):
        mix = _mix(name)
        corpus = traffic.Corpus(mix, 1)
        docs = corpus.file_docs(0)
        words = [len(d.split(" ")) for d in docs]
        assert words == list(corpus.lengths)
        assert min(words) >= lo and max(words) == hi == corpus.longest
        assert len(corpus.longest_positions()) >= mix["length_words"]["longest_per_file"]
        assert len(set(docs)) == len(docs) == mix["docs_per_file"]
    passages = traffic.Corpus(_mix("ingest-passages"), 1).lengths
    assert 60 <= np.median(passages) <= 68  # log-normal, median 64


def test_vocabulary_is_distinct_plain_words():
    vocab = traffic.vocabulary(32768)
    assert len(set(vocab)) == 32768 >= 20000
    assert all(w.isalpha() and w.islower() for w in vocab[:2000])


def test_written_file_is_what_the_program_reads(tmp_path):
    corpus = traffic.Corpus(_mix("ingest-chunks"), 5, docs_per_file=8)
    path = tmp_path / "f.jsonl"
    corpus.write_file(2, str(path))
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["data"] for r in rows] == corpus.file_docs(2)
