"""The comparison that decides `correct`.

What is compared is what the timed path produced: answers of the live
server's /v1/retrieve, asked once the window has closed, about a seeded
sample of the documents that the window itself ingested.  Two queries are
asked of each sampled document: its own text, and a short probe cut from
it (its first few words).  The plain reference (chipbench/reference.py)
embeds the queries, every text the server returned and a seeded pool of
other ingested documents, and three numbers are held to limits:

  retrievable_missing  sampled documents whose own text does not come back
                       first, under its own text, with k results (and
                       probes that got no answer, or a short one): the
                       guarantee "counted as ingested means retrievable",
                       the scatter (right vector under the right key, on
                       the right shard) and the merge across shards.
                       Exact: the limit is 0.
  score_gap            widest |served score - reference cosine| over every
                       (query, returned text) pair: the encoder that made
                       the indexed vector, the encoder of the query, and
                       the scoring.  The probes matter here: documents of
                       random weights lie in a narrow cone, where an
                       encoder's error reaches a cosine only in second
                       order; a short probe lies outside it.
  rank_gap             widest margin by which the reference puts a pool
                       document that was NOT returned above one that was:
                       the top-k is the exact one, not an approximate one.

The limits live in the configuration's file (`limits`), with the readings
they were set from in PERF.md.
"""

from __future__ import annotations

import numpy as np


def compare(
    own: list,
    own_answers: list,
    probes: list,
    probe_answers: list,
    pool_texts: list,
    embed,
    k: int,
) -> dict:
    """own: the sampled documents' texts; probes: the short queries cut
    from them; *_answers[i]: the server's rows ({"text", "score"}, best
    first) or None where the request failed; pool_texts: other ingested
    documents; embed(texts) -> unit vectors.  Returns the three numbers."""
    answered = [rows for rows in own_answers + probe_answers if rows]
    docs = list(dict.fromkeys(
        list(own) + [r["text"] for rows in answered for r in rows] + list(pool_texts)
    ))
    at = {t: i for i, t in enumerate(docs)}
    doc_vecs = embed(docs)
    probe_vecs = embed(list(probes))
    missing = sum(
        1 for query, rows in zip(own, own_answers)
        if not rows or len(rows) != k or rows[0]["text"] != query
    ) + sum(1 for rows in probe_answers if not rows or len(rows) != k)
    score_gap, rank_gap = 0.0, -2.0
    asked = [(doc_vecs[at[q]], rows) for q, rows in zip(own, own_answers)]
    asked += list(zip(probe_vecs, probe_answers))
    for vec, rows in asked:
        if not rows:
            continue
        ref = doc_vecs @ vec
        returned = [at[r["text"]] for r in rows]
        served = np.array([float(r["score"]) for r in rows])
        if not np.isfinite(served).all():
            score_gap = float("inf")
            continue
        score_gap = max(score_gap, float(np.max(np.abs(served - ref[returned]))))
        others = np.ones(len(docs), dtype=bool)
        others[returned] = False
        if others.any():
            rank_gap = max(rank_gap, float(ref[others].max() - ref[returned].min()))
    return {
        "retrievable_missing": missing,
        "score_gap": score_gap,
        "rank_gap": rank_gap,
    }


def control_answers(
    own: list, probes: list, pool_texts: list, embed_low, k: int, index_round=None
) -> tuple:
    """The control's answers: the reference in the lower precision put in
    the program's place — its own top-k over own + pool, with its own
    scores, for the own-text queries and for the probes.  `index_round`
    rounds the stored vectors as a lower-precision index would hold them."""
    docs = list(dict.fromkeys(list(own) + list(pool_texts)))
    stored = embed_low(docs)
    if index_round is not None:
        stored = index_round(stored)
    own_vecs = embed_low(list(own))
    probe_vecs = embed_low(list(probes))

    def top(vec):
        scores = stored @ vec
        best = np.argsort(-scores, kind="stable")[:k]
        return [{"text": docs[i], "score": float(scores[i])} for i in best]

    return [top(v) for v in own_vecs], [top(v) for v in probe_vecs]


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, compared): `compared` has each number beside its limit."""
    compared = {
        name: {"value": numbers[name], "limit": limits[name]}
        for name in ("retrievable_missing", "score_gap", "rank_gap")
    }
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    return correct, compared
