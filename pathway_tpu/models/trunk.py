"""What the models of this package share, below every trunk: layers and
numerics more than one trunk computes, the leaf maker, the slab rule and
the row groups, the refusal of a mesh, and the LM classes.  Imports point
one way: `trunk`, `experts` and `mla` <- the trunks <- what wraps them
(`minilm`, `cross_encoder`, `ops/knn.py`)."""

from __future__ import annotations

import dataclasses
import functools
import importlib
import math
import weakref
from collections import deque
from typing import Any, Callable, Optional

import numpy as np

from pathway_tpu.internals import tracing


def _dtype(name: str):
    import jax.numpy as jnp

    return jnp.bfloat16 if name == "bfloat16" else jnp.float32


@functools.lru_cache(maxsize=None)
def _normal(shape: tuple, fan_in: int, store: str):
    """The program that makes one leaf from a key: N(0, 1/fan_in) drawn in
    float32, kept in `store`.  One program a shape, so the float32 draw
    never reaches HBM."""
    import jax
    import jax.numpy as jnp

    def make(key):
        w = jax.random.normal(key, shape, dtype=jnp.float32) / np.sqrt(fan_in)
        return w.astype(_dtype(store))

    return jax.jit(make)


def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * (1.0 / jnp.sqrt(var + eps)) * scale).astype(x.dtype)


def rope(x, positions, theta, *, freqs=None, interleaved=False):
    """x: [B, H, L, D]; positions: [B, L] absolute token positions.
    `freqs` [D/2] replaces the plain theta ladder (a scaled one, as YaRN's).
    `interleaved`: the pairs are (x[2i], x[2i+1]), as the DeepSeek family
    stores them, and not (x[i], x[i+D/2]); the output is in the split
    layout either way, which q.k does not see as long as q and k agree."""
    import jax.numpy as jnp

    d = x.shape[-1]
    half = d // 2
    if freqs is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs  # B,1,L,half
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
    else:
        x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return out.astype(x.dtype)


def yarn_ladder(dim: int, base: float, factor: float, original_max_len: int,
                beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's frequency ladder [dim / 2] for a rotated part `dim` wide, as
    the DeepSeek family and HF's `yarn` rope type compute it: the plain
    ladder base^(-2i/dim) where a pair turns more than `beta_fast` times
    over the original length, the ladder divided by `factor` where it
    turns less than `beta_slow` times, a linear ramp between.  How the
    pairs are laid out (interleaved, rotate-half) is the caller's."""
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def turns_at(n_rot: float) -> float:
        return dim * math.log(original_max_len / (n_rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def attention(q, k, v, mask, causal: bool, use_flash, mesh=None):
    """Dispatch between the Pallas flash kernel (TPU; O(L) memory) and the
    dense XLA path. q,k,v: [B,H,L,D]; mask: [B,L]. `mesh`: the mesh the
    surrounding jit is partitioned over, when there is one."""
    import jax

    if use_flash is None:
        # flash where O(L^2) score materialization hurts, dense at short L;
        # this gate's crossover is not measured on this machine (ROADMAP
        # queue 3 item 6: it has no cell on either side yet).  The packed
        # side was (PR 28, `packed_attention_fused`): there dense scores
        # lose to a kernel that keeps a slab row in VMEM from L 32-256 up,
        # which says the crossover is low, not where it is for this
        # kernel (f32 operands, head_dim padded to 128 lanes in HBM)
        use_flash = jax.default_backend() == "tpu" and q.shape[2] > 256
    if use_flash:
        from pathway_tpu.ops.kernels import flash_attention

        if mesh is None:
            return flash_attention(q, k, v, mask, causal=causal)
        return _flash_attention_on_mesh(mesh, q, k, v, mask, causal)

    # dense path shares the flash kernel's numerical definition (it is also
    # the kernel's custom_vjp backward), so the two can't drift apart
    from pathway_tpu.ops.kernels.flash_attention import _reference_attention

    return _reference_attention(
        q, k, v, mask, 1.0 / np.sqrt(q.shape[3]), causal
    )


def mesh_axis(mesh, name: str, size: int):
    """`name` if the mesh has that axis and it divides `size`, else None
    (replicated): the shard_map spec of a kernel's batch or head axis."""
    fits = name in mesh.axis_names and size % mesh.shape[name] == 0
    return name if fits else None


def _flash_attention_on_mesh(mesh, q, k, v, mask, causal: bool):
    """Mosaic kernels cannot be partitioned automatically ("wrap the call
    in a shard_map", the TPU compiler says): inside a jit that spans a
    mesh the kernel runs per device under shard_map — batch rows over
    'dp' and heads over 'tp' (where the Megatron qkv split already puts
    them) when they divide, replicated otherwise."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from pathway_tpu.ops.kernels import flash_attention

    dp = mesh_axis(mesh, "dp", q.shape[0])
    tp = mesh_axis(mesh, "tp", q.shape[1])
    qkv = P(dp, tp, None, None)
    return shard_map(
        lambda q, k, v, m: flash_attention(q, k, v, m, causal=causal),
        mesh=mesh,
        in_specs=(qkv, qkv, qkv, P(dp, None)),
        out_specs=qkv,
        check_vma=False,
    )(q, k, v, mask)


def packed_positions(seg):
    """Per-token positions that RESTART at every segment boundary, so a
    packed doc reads the same pos_embed rows it would alone. Computed on
    device from seg (no third wire upload): a token starts a segment
    where seg differs from its left neighbor; cummax propagates each
    segment's start index rightward."""
    import jax
    import jax.numpy as jnp

    l = seg.shape[1]
    pos = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32)[None, :], seg.shape)
    is_start = jnp.concatenate(
        [jnp.ones_like(seg[:, :1], dtype=bool), seg[:, 1:] != seg[:, :-1]],
        axis=1,
    )
    seg_start = jax.lax.cummax(jnp.where(is_start, pos, 0), axis=1)
    return pos - seg_start


def one_chip_only(mesh, module: str, holds: str, elsewhere: str) -> None:
    """The one refusal of a mesh, for the trunks that run a single chip's
    share of a deployment (`moe_mla`, `moe_hybrid`, `longcat`: one
    expert-parallel rank; `eva`, `zaya`: one pipeline stage): `module` holds `holds` on one chip,
    and what would join the chips (`elsewhere`) is not built."""
    if mesh is not None:
        raise NotImplementedError(
            f"{module} runs {holds} on one chip: {elsewhere}, and so a mesh, "
            "is not built (PERF.md section 7)"
        )


def tokenizer(config):
    """The tokenizer a configuration without a checkpoint's vocabulary
    reads texts with: one hashed id a word, from the rows its embedding
    holds."""
    from pathway_tpu.models.tokenizer import HashTokenizer

    return HashTokenizer(vocab_size=config.vocab_size)


# -- slab shapes: what `tokenizer.pack_batch` and `encode_batch` ask ---------------


def seq_bucket(n: int, maximum: Optional[int] = None, *, lane: int, tile: int) -> int:
    """A row's length on a trunk's (lane, tile) grid: whole lanes up to
    one tile, whole tiles above, so that the kernel's tiling divides it
    and lengths that jitter compile one slab (`eva`'s tile is two key
    tiles: every 900-word page, 6,671 +- 45 bytes, lands at 7,168).
    `maximum` caps it, on the same grid."""
    step = lane if n <= tile else tile
    if maximum is not None:
        n = min(n, maximum)
    return -(-max(n, 1) // step) * step


def slab_length(lengths, budget: int, max_len: int = 0, *, lane: int, tile: int,
                cap: int) -> int:
    """The row length of a packed batch of documents `lengths` tokens long.
    Attention costs a token the same wherever its row ends, so a batch
    takes as few rows as it can: one of all its tokens up to a row group
    of `cap` slots (two pages of 3.9k and 6.7k bytes are one row of
    11,264, 6% padding, where two of 7,168 would pad 26%), at most
    PACK_MAX_SEGMENTS documents a row, never less than the budget or the
    longest document; on `seq_bucket`'s grid."""
    from pathway_tpu.models.tokenizer import PACK_MAX_SEGMENTS

    rows = -(-len(lengths) // PACK_MAX_SEGMENTS)
    a_row = min(-(-sum(lengths) // rows), cap)
    return seq_bucket(max(budget, max(lengths), a_row), lane=lane, tile=tile)


def row_bucket(rows: int) -> int:
    """Rows of a packed slab: a power of two up to 8, whole eights above
    (a row is thousands of slots: the encoders' floor of 8 rows would
    multiply a two-page batch by four)."""
    if rows <= 8:
        return 1 << max(rows - 1, 0).bit_length()
    return -(-rows // 8) * 8


def slab_shapes(lane: int, tile: int, cap: int):
    """The `SlabShapes` of a trunk whose rows run up to `cap` slots a row
    group, on its kernel's (lane, tile) grid."""
    from pathway_tpu.models.tokenizer import SlabShapes

    return SlabShapes(
        functools.partial(seq_bucket, lane=lane, tile=tile),
        row_bucket,
        functools.partial(slab_length, lane=lane, tile=tile, cap=cap),
    )


# token slots the trunk takes at a time.  A slab's rows do not see each
# other (attention stays inside a row, routing inside a token), so a slab
# over this runs as equal groups of rows, one after the other inside the
# one program: the activations of a 28k-token ingest slab, 2.7 GB, halve,
# which is what lets two dispatches be in flight beside the parameters and
# the store on a 16 GB chip (PERF.md section 6, PR 30), while a group still
# hands each held expert hundreds of rows
CHUNK_TOKENS = 16384


def row_chunks(rows: int, length: int, cap: Optional[int] = None) -> int:
    """Into how many equal groups of rows a [rows, length] slab is cut:
    the fewest whose groups hold at most `cap` token slots (CHUNK_TOKENS; a
    trunk of another width states its own: `moe_hybrid.ROW_TOKENS`)."""
    cap = CHUNK_TOKENS if cap is None else cap
    for n in range(1, rows + 1):
        if rows % n == 0 and rows // n * length <= cap:
            return n
    return rows


def pooled_by_row_groups(trunk, ids, seg, cap: Optional[int] = None):
    """`trunk(ids, seg) -> (pooled [rows, S, hidden], statistics)` over a
    slab cut into `row_chunks` groups of rows, one after the other inside
    the one program; the groups' statistics summed."""
    import jax

    b, l = ids.shape
    n = row_chunks(b, l, cap)
    if n == 1:
        return trunk(ids, seg)
    pooled, stats = jax.lax.map(
        lambda part: trunk(*part), (ids.reshape(n, b // n, l), seg.reshape(n, b // n, l))
    )
    return (
        pooled.reshape(b, *pooled.shape[2:]),
        {name: per_group.sum(0) for name, per_group in stats.items()},
    )


def document_lengths(seg, max_segments: int) -> np.ndarray:
    """Tokens of each document of a packed batch, int64, from its segment
    ids on the host (seg: [rows, L], 1..max_segments per packed document,
    0 = padding): a row's documents are its runs of one segment id.  What
    the trunks count their batches' tokens and scored pairs from."""
    seg = np.asarray(seg)
    rows = np.arange(seg.shape[0])[:, None] * (int(max_segments) + 1)
    lengths = np.bincount((rows + seg)[seg > 0])
    return lengths[lengths > 0].astype(np.int64)


# -- the LM classes ----------------------------------------------------------------


def model_module(config):
    """The module of the model a configuration belongs to: the one that
    defines the configuration's type.  It has that model's `forward`,
    `init_params`, `param_sharding_rules`, `packed_attention_fused`,
    `tokenizer` and `LM` (`models/transformer.py` for a
    `TransformerConfig`, `models/moe_mla.py` for a `MoeMlaConfig`,
    `models/eva.py` for an `EvaConfig`, `models/moe_hybrid.py` for a
    `MoeHybridConfig`, `models/zaya.py` for a `ZayaConfig`,
    `models/longcat.py` for a `LongcatConfig`; the last five also
    `PACKED`).  Latent attention, which `moe_mla` and `longcat` share, is
    `models/mla.py`, below them as `experts` is.  The one rule by which `TransformerLM`, the encoders
    and the fused programs of `ops/knn.py` find a configuration's model."""
    return importlib.import_module(type(config).__module__)


class TransformerLM:
    """Bundles config+params with jitted entry points."""

    def __init__(self, config, params=None, seed: int = 0):
        import jax

        self.config = config
        model = model_module(config)
        if params is None:
            # the host's time to make and place the parameters (it waits
            # for no device): rows are the leaves
            with tracing.span("setup.weights") as made:
                params = model.init_params(jax.random.PRNGKey(seed), config)
                made.rows = len(jax.tree_util.tree_leaves(params))
        self.params = params

        def _fwd(params, ids, mask, mesh=None):
            # narrow wire dtypes (tokenizer._wire_dtype policy) upcast on
            # device: 16-bit ids/mask halve the token upload vs int32
            import jax.numpy as jnp

            return model.forward(
                params,
                config=self.config,
                ids=ids.astype(jnp.int32),
                mask=mask.astype(jnp.int32),
                mesh=mesh,
            )

        # the mesh (hashable) is static: one executable per mesh and shape
        self._encode_jit = jax.jit(_fwd, static_argnames=("mesh",))

        def _fwd_packed(params, ids, seg, max_segments, mesh=None):
            import jax.numpy as jnp

            return model.forward(
                params,
                config=self.config,
                ids=ids.astype(jnp.int32),
                mask=None,
                seg=seg.astype(jnp.int32),
                max_segments=max_segments,
                mesh=mesh,
            )

        # max_segments is a static one-hot width; callers pass a fixed
        # constant (tokenizer.PACK_MAX_SEGMENTS) so there is one compile
        # per (R, L) slab shape, same cache discipline as the classic path
        self._packed_jit = jax.jit(
            _fwd_packed, static_argnums=(3,), static_argnames=("mesh",)
        )
        self._mesh_params: tuple | None = None

    def mesh_params(self, mesh):
        """Tensor-parallel copy of the weights for a mesh backend: each
        array device_put once under the `param_sharding_rules` partition
        specs (qkv/up column-, out/down row-sharded on 'tp'), cached per
        mesh. `self.params` — and every caller that doesn't opt in via
        the `params=` override — keeps its exact single-device layout."""
        cached = self._mesh_params
        if cached is not None and cached[0] is mesh:
            return cached[1]
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        rules = model_module(self.config).param_sharding_rules(self.config, mesh)
        shardings = jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec),
            rules,
            is_leaf=lambda x: isinstance(x, P),
        )
        placed = jax.device_put(self.params, shardings)
        self._mesh_params = (mesh, placed)
        return placed

    def encode_packed(self, ids, seg, max_segments: int, *, params=None,
                      mesh=None):
        """Packed ragged encode: ids/seg from tokenizer.pack_batch (wire
        dtypes; upcast on device). Returns [R, max_segments, H] pooled
        L2-normalized vectors; empty slots are zero. Inputs are NOT
        donated — the device-side int upcast changes the buffer dtype, so
        XLA could never reuse them and would warn on every dispatch.
        `mesh`: pass it whenever params or inputs are sharded over one."""
        return self._packed_jit(
            self.params if params is None else params,
            ids,
            seg,
            int(max_segments),
            mesh=mesh,
        )

    def __call__(self, ids, mask, *, params=None, mesh=None):
        # ids/mask arrive already wire-narrowed by encode_batch (tokenizer
        # _wire_dtype is the single policy); no host casts here — a cast
        # would pull mesh-sharded inputs back to host and destroy their
        # NamedSharding placement.  `mesh`: pass it whenever params or
        # inputs are sharded over one (see forward)
        return self._encode_jit(
            self.params if params is None else params,
            ids=ids,
            mask=mask,
            mesh=mesh,
        )


@dataclasses.dataclass(frozen=True)
class PackedTrunk:
    """What a packed decoder trunk's module (its `PACKED`) tells
    `PackedTrunkLM`: its packed program's name in the device trace; what
    `one_chip_only` says of it under a config; `count_batch(config, ids,
    seg, lengths)`, a batch's host counters; `count_stats(config, stats)`,
    a finished dispatch's, where `forward(..., with_stats=True)` returns
    statistics (None: the program returns the pooled vectors alone)."""

    program: str
    one_chip: Callable[[Any], tuple]
    count_batch: Optional[Callable[..., None]] = None
    count_stats: Optional[Callable[[Any, dict], None]] = None


# the models whose statistics a reading of the span record first brings up
# to date.  Weak: the record outlives a model and may not keep one (and
# its parameters) alive
_LIVE: "weakref.WeakSet[PackedTrunkLM]" = weakref.WeakSet()


def _count_finished() -> None:
    """Before a reading of the record: count what the device has finished,
    never waiting (a /status request must not hang behind a dispatch)."""
    for lm in list(_LIVE):
        lm.count_stats(wait=False)


class PackedTrunkLM(TransformerLM):
    """`TransformerLM` for a packed decoder trunk, as its module's
    `PACKED` describes it: the same entry points, the packed program under
    the trunk's own name, each packed batch's host counters, and, where
    the program returns statistics, those folded into the span record's
    counters once the device has produced them."""

    def __init__(self, config, params=None, seed: int = 0):
        import jax

        super().__init__(config, params=params, seed=seed)
        model = model_module(config)
        packed = model.PACKED
        stats = {} if packed.count_stats is None else {"with_stats": True}

        def program(params, ids, seg, max_segments):
            import jax.numpy as jnp

            return model.forward(
                params, config, ids.astype(jnp.int32), None,
                seg=seg.astype(jnp.int32), max_segments=max_segments, **stats,
            )

        program.__name__ = program.__qualname__ = packed.program
        self._packed_jit = jax.jit(program, static_argnums=(3,))
        self._stats: deque = deque()  # of dispatches not yet counted
        if stats:
            _LIVE.add(self)
            tracing.on_read(_count_finished)

    def encode_packed(self, ids, seg, max_segments: int, *, params=None,
                      mesh=None):
        packed = model_module(self.config).PACKED
        one_chip_only(mesh, *packed.one_chip(self.config))
        if packed.count_batch is not None:
            packed.count_batch(self.config, ids, seg, document_lengths(seg, max_segments))
        return self._dispatch(params, ids, seg, int(max_segments))

    def _dispatch(self, params, ids, seg, max_segments: int):
        """The packed program's launch; its statistics, where it returns
        them, queued to be counted."""
        out = self._packed_jit(self.params if params is None else params, ids, seg, max_segments)
        if model_module(self.config).PACKED.count_stats is None:
            return out
        pooled, stats = out
        self._stats.append(stats)
        self.count_stats(wait=False)
        return pooled

    def count_stats(self, wait: bool = True) -> None:
        """Adds the finished dispatches' statistics to the counters: with
        `wait=False` (the dispatch thread after a launch, a reading of the
        record) only what the device has already produced, in dispatch
        order, so neither ever blocks on the device."""
        count = model_module(self.config).PACKED.count_stats
        while self._stats:
            try:
                stats = self._stats.popleft()
            except IndexError:  # another thread counted it
                return
            if not wait and not stats["tokens"].is_ready():
                self._stats.appendleft(stats)
                return
            count(self.config, stats)
