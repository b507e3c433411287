"""The comparison on vectors made by hand: each number moves for the fault
it is there to catch, and for no other."""

import numpy as np

from chipbench import compare

K = 2


def _world():
    # six "documents" on a circle, 10 degrees apart; a probe near document 0
    angles = {f"d{i}": np.deg2rad(10 * i) for i in range(6)}
    angles["p0"] = np.deg2rad(-20)
    vec = {t: np.array([np.cos(a), np.sin(a)]) for t, a in angles.items()}

    def embed(texts):
        return np.stack([vec[t] for t in texts])

    def rows(query, texts):
        return [{"text": t, "score": float(vec[query] @ vec[t])} for t in texts]

    return embed, rows


def _numbers(own_answers, probe_answers):
    embed, _ = _world()
    return compare.compare(
        ["d0", "d3"], own_answers, ["p0"], probe_answers,
        ["d1", "d2", "d4", "d5"], embed, K,
    )


def test_exact_answers_read_zero():
    _, rows = _world()
    n = _numbers([rows("d0", ["d0", "d1"]), rows("d3", ["d3", "d2"])],
                 [rows("p0", ["d0", "d1"])])
    assert n["retrievable_missing"] == 0
    assert n["score_gap"] < 1e-12
    assert n["rank_gap"] <= 1e-12  # d3's neighbours d2 and d4 tie exactly


def test_each_fault_moves_its_number():
    _, rows = _world()
    good_own = [rows("d0", ["d0", "d1"]), rows("d3", ["d3", "d2"])]
    good_probe = [rows("p0", ["d0", "d1"])]
    # a document that cannot be retrieved: its own text is not first
    n = _numbers([rows("d0", ["d1", "d2"]), good_own[1]], good_probe)
    assert n["retrievable_missing"] == 1 and n["rank_gap"] > 0.01
    # a failed request, and one with too few rows
    assert _numbers([None, good_own[1]], good_probe)["retrievable_missing"] == 1
    assert _numbers([good_own[0][:1], good_own[1]], good_probe)["retrievable_missing"] == 1
    # an altered score
    bent = [dict(r, score=r["score"] + 0.01) for r in good_probe[0]]
    n = _numbers(good_own, [bent])
    assert abs(n["score_gap"] - 0.01) < 1e-9 and n["retrievable_missing"] == 0
    # a top-k that is not the exact one: d2 returned for the probe, d1 left out
    n = _numbers(good_own, [rows("p0", ["d0", "d2"])])
    _, _ = n, None
    assert n["rank_gap"] > 0.05 and n["score_gap"] < 1e-12


def test_verdict_holds_each_number_to_its_limit():
    limits = {"retrievable_missing": 0, "score_gap": 0.003, "rank_gap": 0.003}
    ok, compared = compare.verdict(
        {"retrievable_missing": 0, "score_gap": 0.001, "rank_gap": -0.2}, limits)
    assert ok and compared["score_gap"] == {"value": 0.001, "limit": 0.003}
    for name, bad in (("retrievable_missing", 1), ("score_gap", 0.0031), ("rank_gap", 0.01)):
        numbers = {"retrievable_missing": 0, "score_gap": 0.0, "rank_gap": 0.0, name: bad}
        assert not compare.verdict(numbers, limits)[0]


def test_control_answers_are_the_lower_precisions_own_top_k():
    embed, _ = _world()
    own_rows, probe_rows = compare.control_answers(
        ["d0"], ["p0"], ["d1", "d2", "d3"], embed, K)
    assert [r["text"] for r in own_rows[0]] == ["d0", "d1"]
    assert [r["text"] for r in probe_rows[0]] == ["d0", "d1"]
