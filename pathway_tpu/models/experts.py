"""The expert layer every MoE trunk runs (`moe_mla`, `moe_hybrid`,
`zaya`, `longcat`): a router's top-k (`route`), the routed experts one
chip holds (`held_experts`, whose return the configuration's shape
chooses), the zero-compute experts every chip computes alike
(`zero_expert_part`), SwiGLU (`swiglu`), and the counters `moe.*` of a
finished dispatch (`count_stats`)."""

from __future__ import annotations

from typing import Optional

import numpy as np


def swiglu(h, gate, up, down):
    import jax

    dt = h.dtype
    return (jax.nn.silu(h @ gate.astype(dt)) * (h @ up.astype(dt))) @ down.astype(dt)


def route(h, router, config, bias=None, *, softmax: bool = False, normalise: bool = True):
    """h: [T, hidden] -> (experts [T, k] int32, weights [T, k] f32): top-k
    over all the router's scores (no group limit), sigmoid scores whose
    chosen ones are normalised to sum to one and scaled.  `bias` [the
    router's outputs], where a model has one (`e_score_correction_bias`,
    `topk_method` "noaux_tc"), is added to the scores for the selection
    alone: it says which experts, never how much of each.  Without one
    this is plain top-k, as it was.  `softmax`: the scores are a softmax
    over every output of the router, which may outnumber the routed
    experts (LongCat-Flash's 512 routed and 256 zero-compute experts:
    `zero_expert_part`); `normalise` false: the weights are the scaled
    scores themselves (`norm_topk_prob` false).  The logits are f32:
    products of the compute dtype's operands, accumulated in f32.
    `config`: any trunk's with `experts_per_token` and
    `routed_scaling_factor` (`models/moe_mla.py`, `models/moe_hybrid.py`,
    `models/longcat.py`)."""
    import jax
    import jax.numpy as jnp

    logits = jnp.dot(h, router.astype(h.dtype), preferred_element_type=jnp.float32)
    scores = jax.nn.softmax(logits, axis=-1) if softmax else jax.nn.sigmoid(logits)
    if bias is None:
        top, experts = jax.lax.top_k(scores, config.experts_per_token)
    else:
        _, experts = jax.lax.top_k(
            scores + bias.astype(jnp.float32), config.experts_per_token
        )
        top = jnp.take_along_axis(scores, experts, axis=-1)
    weights = config.routed_scaling_factor * top
    if normalise:
        weights = weights / top.sum(-1, keepdims=True)
    return experts.astype(jnp.int32), weights


def zero_expert_part(h, experts, weights, n_routed: int):
    """The zero-compute experts' part of an expert layer: sum over a
    token's selected experts e >= `n_routed` (each the identity) of w_e h,
    as one [T] coefficient times h, in h's dtype.  Every chip computes it
    for its own tokens; no such pair is held, bucketed or counted as held
    (`held_experts` holds ids in [expert_offset, expert_offset +
    experts_held) only).  h: [T, hidden]; experts, weights: `route`'s."""
    import jax.numpy as jnp

    coef = jnp.sum(jnp.where(experts >= n_routed, weights, 0.0), axis=-1)
    return coef.astype(h.dtype)[:, None] * h


# a tile of the TPU's grouped matmul: it works a tile and a group at a time
# (12 groups of 256 rows took the time of 12 of 512, and 12 of 384, every
# second of which lies across two tiles, half as much again: chip runs, PR
# 33), so the buffer's rows come in whole tiles and a group begins one
PAIR_ROWS = 512


def pair_capacity(tokens: int, config) -> int:
    """Rows of the static buffer of (token, held expert) pairs for a slab
    of `tokens` slots, from the slots, k = `experts_per_token` and the
    share held / routed = `experts_held` / `n_routed_experts`, for any k
    and share.  The most pairs a slab can put on held experts is `most` =
    tokens x min(k, held).  Up to 4,096 rows that many: small batches
    cannot overflow.  Above that one row a token slot.  The expected load
    is tokens x k x held / routed: where that is under the rows (half a
    row a token at a sixteenth of the experts held, top-8; the slab's
    padding routes nothing), the rows themselves are the room that lets
    every group begin a tile (`held_experts`), and a skewed router's pairs
    still fit one after the other.  Where it fills them (every expert held:
    k pairs a token), the rows are `most` and a tile a held expert, which
    is the most that beginning each group on a tile can take: no pair can
    fall beyond the buffer (top-1 of 16: 28,224 slots -> 36,864 rows; top-8
    of 256: 23,552 -> 319,488).  Rounded up to whole tiles: at 14,112
    rows, which 512 does not divide, the TPU's grouped matmul took 8.6 ms
    where it takes 2.5 at 14,336 (chip runs, PR 30)."""
    most = tokens * min(config.experts_per_token, config.experts_held)
    rows = min(most, max(tokens, 4096))
    if tokens * config.experts_per_token * config.experts_held >= rows * config.n_routed_experts:
        rows = most + config.experts_held * PAIR_ROWS
    return -(-rows // PAIR_ROWS) * PAIR_ROWS


def combine_rows(tokens: int, config) -> int:
    """Slots of the compact list of tokens with two or more pairs in the
    buffer (`held_experts`' return), for any k and share.  At k = 1 there
    are no such tokens and no list.  A slot a token (the tokens are the
    list, which cannot spill): a slab of at most 4,096 token slots (the
    buffer's least size), whose program stays as small as it was, which is
    what the search programs' query slabs are loaded for; and a share where
    a token expects a held pair or more (k x held / routed >= 1: every
    expert held at top-8 gives every real token 8).  Otherwise an eighth
    of the token slots in whole tiles: 2,048 for 14,112, where k x held /
    routed = 1/2 (a sixteenth of the experts held, top-8) gives about 915
    such tokens."""
    if config.experts_per_token == 1:
        return 0
    if (tokens <= 4096
            or config.experts_per_token * config.experts_held >= config.n_routed_experts):
        return tokens
    return -(-tokens // (8 * PAIR_ROWS)) * PAIR_ROWS


def returns_fused(config) -> bool:
    """Whether `held_experts` returns the pairs in one fused weighted sum at
    the tokens' side: k > 1 with every routed expert held.  Then every real
    token has k pairs and the buffer is k rows a token and a tile a held
    expert (13.6 rows a token slot at top-8 of 256), so a pass over the
    buffer costs many over the tokens, and a loop bounded by the busiest
    token always makes k passes.  Where a sixteenth of the experts is held
    (about a row a token, 1-8 pairs) the compact list and the buffer-side
    weight stay; at k = 1 there is no sum."""
    return config.experts_per_token > 1 and config.experts_held >= config.n_routed_experts


def held_experts(h, valid, layer, config, capacity: Optional[int] = None,
                 *, listed: Optional[int] = None, with_stats: bool = False,
                 routing=None):
    """The routed experts' part of an expert layer that this rank
    computes.  h: [T, hidden] (normed), valid: [T] bool (padding routes
    nothing).  Returns (y [T, hidden], tokens per held expert
    [experts_held] int32, pairs selected and held but beyond the buffer
    () int32), and with `with_stats` a fourth: {"multi_pair_tokens",
    "combine_spills", "groups_aligned", "groups_packed", "group_rows",
    "group_pad_rows"}, each () int32, and "fused_returns" (1) where the
    return is fused.  `capacity` and `listed` override `pair_capacity` and
    `combine_rows` (tests).  `config`: a trunk's with the routing
    fields, k =
    `experts_per_token`, `experts_held` of `n_routed_experts` from
    `expert_offset`, any k and any share (`models/moe_hybrid.py`: a
    sixteenth of 256 experts at width 4096 under a selection bias
    `layer["router_bias"]`, or all 256 of width 512 at top-8;
    `models/zaya.py`: all 16 at top-1); the shared expert, where a model
    has one, is the caller's.  `routing`: (experts [T, k] int32, weights [T, k] f32) from
    a trunk whose router is not `route`'s matrix and sigmoid
    (`models/zaya.py`: an MLP over a state carried from layer to layer, a
    softmax, and a choice beyond the routed experts, "skip", which is an
    expert nobody holds); None: `route(h, layer["router"], ...)`.

    Selected pairs on held experts are sorted by expert into a buffer of
    `capacity` rows, and three grouped matmuls (gate, up, down) run over
    the groups' rows.  The TPU's grouped matmul works a 512-row tile and a
    group at a time, so a group of 476 rows that begins in the middle of a
    tile costs two tiles' time: 12 such groups one after the other took
    6.74 ms for the three matmuls whatever the buffer's size (14,336 rows
    or 7,168), and 4.17 ms with every group moved to a tile's first row
    (chip runs, PR 33).  So each group begins a tile where the buffer has
    the room (12 to 17 of its 28 tiles at the ingest slab), and otherwise
    the groups follow each other as the overflow count assumes: the layout
    is data (`sizes`), not a second program.  Which pair a buffer row
    holds follows from its group's shift and the end of its pairs laid
    along the rows by a running sum of their steps at the groups' ends:
    no [experts_held, rows] mask (84 M elements a layer at 256 held) and
    no search.  A search of the ends with its gathers of a group's values
    took 31 ms a layer-pass at 319,488 rows, a quarter of the Laguna
    cell's busy time: on the TPU a gather of one element costs about 10
    ns (chip runs, PR 44).  A pair's row is its rank in the sorted order
    plus its group's shift, read through the fused [experts_held, pairs]
    compare that also counts the groups.  `group_rows` counts the rows the
    grouped matmuls run over, `group_pad_rows` those of them that hold no
    pair.

    With every expert held at k > 1 (`returns_fused`: 319,488 buffer rows
    for 23,552 token slots at top-8 of 256) a token's k rows come back in
    one fused weighted sum, the weight put on at the token's side, in slot
    order and in the compute dtype: a pass over the buffer to put the
    weights on, their one-element gathers and a loop of k passes over the
    tokens took 55 ms a dispatch of four expert layers on a TPU v5e, 18.5%
    of the Laguna cell's busy time, and the fused sum takes about half of
    it; `fused_returns` counts such passes.  Otherwise the results go back
    to their tokens in one gather a token.  A pair's weight is put on its
    row in the buffer.  A token with one pair reads that row, a token with
    none a zero: at k = 1 that is the whole return, one inverse permutation.
    The tokens with two or more (8% at k x held / routed = 1/2: a sixteenth
    of the experts held, top-8) are first summed, in the compute dtype and
    in their slots' order, in a list of `listed` slots appended to the
    buffer, and read their sum.  More such tokens than slots is seen in the
    input: then the others' further pairs are added pass by
    pass over every token, and no pair is dropped.  Where the list has a
    slot a token (a small slab, or a share at which every token expects
    several pairs: `combine_rows`), the tokens are the list, their sums
    are `y`, one gather a pass and k passes at most, and nothing can
    spill: the search programs' query slabs stay as small
    as they were (a program's load from the compile cache took 0.28 s for
    0.15 with the list built there too, chip runs, PR 33).  At [14112,
    7168] the return took 7.1 ms as one pass over every token for every
    held pair the busiest token has (4 or 5 of its 8; 1.5 ms a pass in the
    program) and takes 3.8 (chip runs, PR 33); a scatter-add of the
    buffer's rows took 14.0 ms (chip runs, PR 30)."""
    import jax
    import jax.numpy as jnp

    c = config
    t, k, n_held = h.shape[0], c.experts_per_token, c.experts_held
    capacity = pair_capacity(t, c) if capacity is None else capacity
    listed = combine_rows(t, c) if listed is None else listed
    if routing is None:
        routing = route(h, layer["router"], c, layer.get("router_bias"))
    experts, weights = routing
    local = experts - c.expert_offset
    held = (local >= 0) & (local < n_held) & valid[:, None]
    group = jnp.where(held, local, n_held).reshape(-1)  # [T*k]; n_held = not ours
    member = group[None, :] == jnp.arange(n_held, dtype=jnp.int32)[:, None]
    counts = jnp.sum(member, axis=1, dtype=jnp.int32)
    tiles = -(-counts // PAIR_ROWS) * PAIR_ROWS
    aligned = tiles.sum() <= capacity
    ends = jnp.minimum(jnp.cumsum(jnp.where(aligned, tiles, counts)), capacity)
    sizes = jnp.diff(ends, prepend=0)  # the groups as the grouped matmul sees them
    starts = ends - sizes
    kept = jnp.minimum(counts, sizes)  # the pairs of each that the buffer holds
    overflow = counts.sum() - kept.sum()
    order = jnp.argsort(group, stable=True)  # held pairs first, by expert
    first = jnp.cumsum(counts) - counts  # a group's first pair in that order
    shift = starts - first  # how far down the buffer from there its rows lie
    dt = h.dtype

    def of(per_group, mask):  # mask: [experts_held, n] bool, one group a column at most
        return jnp.sum(jnp.where(mask, per_group[:, None], 0), axis=0)

    # the pair a buffer row holds, if any: its group's shift and the end of
    # its pairs laid along the rows, a running sum of their steps at the
    # groups' ends (a row at or past the last end reads the last group's)
    at = jnp.arange(capacity, dtype=jnp.int32)
    per_group = jnp.stack([shift, starts + kept], axis=1)
    steps = jnp.zeros((capacity, 2), jnp.int32).at[ends[:-1]].add(
        per_group[1:] - per_group[:-1], mode="drop"
    )
    along = per_group[0] + jnp.cumsum(steps, axis=0)
    filled = at < along[:, 1]
    pair = jnp.where(filled, order[jnp.clip(at - along[:, 0], 0, t * k - 1)], 0)
    rows = h[pair // k]  # [capacity, hidden]
    with jax.named_scope("expert_matmul"):
        gate = jax.lax.ragged_dot(rows, layer["experts_gate"].astype(dt), sizes)
        up = jax.lax.ragged_dot(rows, layer["experts_up"].astype(dt), sizes)
        out = jax.lax.ragged_dot(
            jax.nn.silu(gate) * up, layer["experts_down"].astype(dt), sizes
        )
    fused = returns_fused(c)
    if not fused:
        # a pair's weight goes on here.  The other rows hold no pair or were
        # never written: a zero weight does not silence what they hold
        weight = weights.reshape(-1)[pair].astype(dt)
        out = jnp.where(filled[:, None], weight[:, None] * out, jnp.zeros_like(out))
    # a pair's row in the buffer; each token's pairs that are in it moved
    # to the front of its k slots
    nth_sorted = jnp.argsort(order).astype(jnp.int32)
    mine = held & (nth_sorted < of(first + kept, member)).reshape(t, k)
    row = (nth_sorted + of(shift, member)).reshape(t, k)
    nth = jnp.cumsum(mine, axis=1) - 1
    slot = mine[:, :, None] & (nth[:, :, None] == jnp.arange(k)[None, None, :])
    row_of = jnp.sum(jnp.where(slot, row[:, :, None], 0), axis=1)  # [T, k]
    pairs_of = jnp.sum(mine, axis=1, dtype=jnp.int32)  # [T]
    multi = pairs_of > 1
    n_multi = jnp.sum(multi, dtype=jnp.int32)

    def nth_pair(rows_of, j, has):
        at_j = jax.lax.dynamic_slice_in_dim(rows_of, j, 1, axis=1)[:, 0]
        return jnp.where(has[:, None], out[at_j], jnp.zeros((), dt))

    def summed(rows_of, pairs):
        """Each entry's pairs, in the compute dtype as the residual stream
        is, in their slots' order: as many passes as the busiest has."""
        return jax.lax.fori_loop(
            1, jnp.max(pairs),
            lambda j, acc: acc + nth_pair(rows_of, j, j < pairs),
            nth_pair(rows_of, 0, 0 < pairs),
        )

    if k == 1:  # a pair a token at most: one inverse permutation
        y = jnp.where(mine, out[row[:, 0]], jnp.zeros((), dt))
    elif fused:
        # a token's k rows gathered, weighted and summed in one expression,
        # in slot order; the mask after the product, so that a row no pair
        # filled is never read into `y`
        w_of = jnp.sum(jnp.where(slot, weights[:, :, None], 0.0), axis=1).astype(dt)

        def term(j):
            return jnp.where((j < pairs_of)[:, None], w_of[:, j, None] * out[row_of[:, j]],
                             jnp.zeros((), dt))

        y = term(0)
        for j in range(1, k):
            y = y + term(j)
    elif listed >= t:  # a slot a token: the tokens are the list
        y = summed(row_of, pairs_of)
    else:
        # the tokens with two or more pairs, in the list's slots (the search
        # compares every slot with every token: 0.02 ms where the binary
        # search's loop took 0.21, chip runs, PR 33)
        listed_at = jnp.cumsum(multi, dtype=jnp.int32) - 1
        in_list = multi & (listed_at < listed)
        token_of = jnp.minimum(
            jnp.searchsorted(
                listed_at, jnp.arange(listed, dtype=jnp.int32), method="compare_all"
            ),
            t - 1,
        )
        comb = summed(
            row_of[token_of],
            jnp.where(jnp.arange(listed) < n_multi, pairs_of[token_of], 0),
        )
        y = jnp.concatenate([out, comb])[
            jnp.where(in_list, capacity + listed_at, row_of[:, 0])
        ]
        y = jnp.where((pairs_of > 0)[:, None], y, jnp.zeros_like(y))
        # more such tokens than slots: the others' further pairs, in as
        # many passes over every token as the busiest of them has pairs
        spilled = multi & ~in_list
        y = jax.lax.fori_loop(
            1, jnp.where(n_multi > listed, jnp.max(pairs_of), 1),
            lambda j, y: y + nth_pair(row_of, j, spilled & (j < pairs_of)), y,
        )
    if not with_stats:
        return y, counts, overflow
    stats = {
        "multi_pair_tokens": n_multi,
        "combine_spills": (n_multi > listed).astype(jnp.int32),
        "groups_aligned": aligned.astype(jnp.int32),
        "groups_packed": 1 - aligned.astype(jnp.int32),
        "group_rows": ends[-1],
        "group_pad_rows": ends[-1] - kept.sum(),
    }
    if fused:
        stats["fused_returns"] = jnp.int32(1)
    return y, counts, overflow, stats


# what `held_experts` counts of a pass (a row group's pass through one expert
# layer) beside the tokens per expert and the overflow: each a counter
# `moe.<name>`.  "fused_returns" is in the statistics only where the return
# is fused (`returns_fused`): elsewhere its counter reads 0 and the program
# has no output that always reads 0
LAYER_PASS_STATS = (
    "multi_pair_tokens", "combine_spills", "groups_aligned", "groups_packed",
    "group_rows", "group_pad_rows", "fused_returns",
)


def layer_pass_lists(config) -> dict:
    """What a trunk gathers of its expert layers' passes, a list a name
    that begins empty: "expert_tokens" [layers, experts_held], "overflow"
    and each of LAYER_PASS_STATS that `held_experts` gives under `config`
    [layers]."""
    import jax.numpy as jnp

    stats = {"expert_tokens": [jnp.zeros((0, config.experts_held), jnp.int32)]}
    for name in ("overflow",) + LAYER_PASS_STATS:
        if name != "fused_returns" or returns_fused(config):
            stats[name] = [jnp.zeros((0,), jnp.int32)]
    return stats


def count_stats(config, stats) -> None:
    """A finished dispatch's statistics into the counters `moe.*`."""
    from pathway_tpu.internals import tracing

    k = config.experts_per_token
    per_expert = np.asarray(stats["expert_tokens"])
    layers = per_expert.shape[0]
    tracing.add("moe.pairs_routed", n=int(stats["tokens"]) * k * layers)
    tracing.add("moe.pairs_held", n=int(per_expert.sum()))
    tracing.add("moe.expert_tokens_max", n=int(per_expert.max(axis=1).sum()))
    tracing.add(
        "moe.expert_tokens_mean", n=int(round(per_expert.mean(axis=1).sum()))
    )
    tracing.add("moe.overflow_pairs", n=int(np.asarray(stats["overflow"]).sum()))
    for name in LAYER_PASS_STATS:
        tracing.add("moe." + name, n=int(np.asarray(stats.get(name, 0)).sum()))
