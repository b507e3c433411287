"""Decoder-only LM, Mistral-7B-class architecture, TPU-first.

Replaces the reference's local torch pipeline (xpacks/llm/llms.py
HFPipelineChat:456) with an in-tree JAX decoder: GQA (8 kv heads vs 32 q
heads), RoPE, RMSNorm, SwiGLU — the Mistral-7B recipe — with

  * prefill via the Pallas flash-attention kernel (causal, O(L) memory);
  * a preallocated, donated KV cache ([B, kv_heads, max_len, hd] per layer)
    updated in place with lax.dynamic_update_slice;
  * the whole generation loop as ONE jit (lax.scan over steps): no host
    round trip per token, greedy or temperature sampling on device;
  * Megatron tensor-parallel PartitionSpecs (q/k/v/gate/up column-sharded,
    o/down row-sharded, cache sharded over kv heads on 'tp').
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import numpy as np

from pathway_tpu.models.trunk import attention, rms_norm, rope


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int = 32000
    hidden: int = 4096
    layers: int = 32
    q_heads: int = 32
    kv_heads: int = 8
    mlp_dim: int = 14336
    max_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    @property
    def head_dim(self) -> int:
        return self.hidden // self.q_heads


MISTRAL_7B_DECODER = DecoderConfig()

TINY = DecoderConfig(
    vocab_size=1024, hidden=64, layers=2, q_heads=4, kv_heads=2,
    mlp_dim=128, max_len=128, dtype="float32",
)


def init_decoder_params(rng, config: DecoderConfig) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    h, hd = config.hidden, config.head_dim
    kv_dim = config.kv_heads * hd
    keys = jax.random.split(rng, 2 + config.layers)
    scale = 0.02
    # store params in the config dtype: a 7B-class config in bf16 is
    # 14 GB and fits a single v5e; float32 storage would not (the
    # forward already computes in config.dtype either way)
    param_dtype = (
        jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32
    )

    def dense(key, shape):
        return (
            jax.random.normal(key, shape, dtype=jnp.float32) * scale
        ).astype(param_dtype)

    params: Dict[str, Any] = {
        "embed": dense(keys[0], (config.vocab_size, h)),
        "ln_f": jnp.ones((h,)),
        "layers": [],
    }
    for i in range(config.layers):
        k = jax.random.split(keys[2 + i], 7)
        params["layers"].append(
            {
                "ln1": jnp.ones((h,)),
                "ln2": jnp.ones((h,)),
                "wq": dense(k[0], (h, h)),
                "wk": dense(k[1], (h, kv_dim)),
                "wv": dense(k[2], (h, kv_dim)),
                "wo": dense(k[3], (h, h)),
                "gate": dense(k[4], (h, config.mlp_dim)),
                "up": dense(k[5], (h, config.mlp_dim)),
                "down": dense(k[6], (config.mlp_dim, h)),
            }
        )
    return params


def decoder_sharding_rules(config: DecoderConfig, mesh):
    """Megatron TP specs on the mesh's 'tp' axis."""
    from jax.sharding import PartitionSpec as P

    tp = "tp" if "tp" in mesh.axis_names else None
    layer = {
        "ln1": P(None),
        "ln2": P(None),
        "wq": P(None, tp),
        "wk": P(None, tp),
        "wv": P(None, tp),
        "wo": P(tp, None),
        "gate": P(None, tp),
        "up": P(None, tp),
        "down": P(tp, None),
    }
    return {
        "embed": P(tp, None),
        "ln_f": P(None),
        "layers": [dict(layer) for _ in range(config.layers)],
    }


def _repeat_kv(x, n_rep: int):
    import jax.numpy as jnp

    if n_rep == 1:
        return x
    b, h, l, d = x.shape
    return jnp.broadcast_to(
        x[:, :, None, :, :], (b, h, n_rep, l, d)
    ).reshape(b, h * n_rep, l, d)


def init_kv_cache(config: DecoderConfig, batch: int):
    """Preallocated cache pytree: per layer {'k','v'} [B, KVH, max_len, hd]."""
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32
    shape = (batch, config.kv_heads, config.max_len, config.head_dim)
    return [
        {"k": jnp.zeros(shape, dtype=dtype), "v": jnp.zeros(shape, dtype=dtype)}
        for _ in range(config.layers)
    ]


def decoder_forward(params, config: DecoderConfig, ids, mask, *,
                    positions=None, kv_cache=None, kv_valid=None,
                    slot_offset=0, use_flash=None):
    """ids, mask: [B, L] (left-aligned prompts).

    Cacheless mode (kv_cache is None): plain causal attention over the
    batch (prefill-style scoring; flash kernel on TPU).

    Cache mode: writes this call's K/V into slots [slot_offset,
    slot_offset+L) of the preallocated cache and attends over every cache
    slot j with kv_valid[b, j] == 1 and j <= (slot_offset + query index) —
    slot order equals sequence order for left-aligned prompts, so slot
    causality is token causality. `positions` feeds RoPE with each row's
    true token position (ragged lengths ⇒ positions differ from slots
    during decode).

    Returns (logits [B, L, V] f32, new_cache).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    compute_dtype = (
        jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32
    )
    b, l = ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(l), (b, l))
    x = params["embed"][ids].astype(compute_dtype)
    qh, kvh, hd = config.q_heads, config.kv_heads, config.head_dim
    n_rep = qh // kvh
    new_cache = [] if kv_cache is not None else None

    is_prefill = (
        kv_cache is not None
        and isinstance(slot_offset, int)
        and slot_offset == 0
        and l > 1
    )
    if kv_cache is not None and not is_prefill:
        # [B, L, max_len] attention mask shared by all layers (decode /
        # chunked-prefill path; initial prefill uses the flash path below)
        slot_idx = jnp.arange(config.max_len)[None, None, :]
        q_slot = slot_offset + jnp.arange(l)[None, :, None]
        attend = (slot_idx <= q_slot) & (
            kv_valid[:, None, :].astype(bool)
        )

    for li, layer in enumerate(params["layers"]):
        y = rms_norm(x, layer["ln1"], config.norm_eps)
        q = (y @ layer["wq"].astype(compute_dtype)).reshape(b, l, qh, hd)
        k = (y @ layer["wk"].astype(compute_dtype)).reshape(b, l, kvh, hd)
        v = (y @ layer["wv"].astype(compute_dtype)).reshape(b, l, kvh, hd)
        q = rope(q.transpose(0, 2, 1, 3), positions, config.rope_theta)
        k = rope(k.transpose(0, 2, 1, 3), positions, config.rope_theta)
        v = v.transpose(0, 2, 1, 3)

        if kv_cache is not None:
            ck = lax.dynamic_update_slice(
                kv_cache[li]["k"], k.astype(kv_cache[li]["k"].dtype),
                (0, 0, slot_offset, 0),
            )
            cv = lax.dynamic_update_slice(
                kv_cache[li]["v"], v.astype(kv_cache[li]["v"].dtype),
                (0, 0, slot_offset, 0),
            )
            new_cache.append({"k": ck, "v": cv})
            if is_prefill:
                # Prefill: no cache slots beyond this call's L can be
                # valid, so attention over the cache reduces to causal
                # attention over this call's own K/V (keys masked by
                # kv_valid's first L slots, per the cache-mode contract) —
                # O(L) flash path instead of a dense [B, H, L, max_len]
                # f32 score matrix.
                ctx = attention(
                    q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep),
                    kv_valid[:, :l], True, use_flash,
                ).astype(compute_dtype)
            else:
                s = jnp.einsum(
                    "bhqd,bhkd->bhqk", q.astype(jnp.float32),
                    _repeat_kv(ck.astype(jnp.float32), n_rep),
                    preferred_element_type=jnp.float32,
                ) / np.sqrt(hd)
                s = jnp.where(attend[:, None, :, :], s, -1e30)
                p = jnp.exp(s - s.max(-1, keepdims=True))
                p = p / (p.sum(-1, keepdims=True) + 1e-30)
                ctx = jnp.einsum(
                    "bhqk,bhkd->bhqd", p.astype(compute_dtype),
                    _repeat_kv(cv.astype(compute_dtype), n_rep),
                )
        else:
            ctx = attention(
                q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep), mask,
                True, use_flash,
            ).astype(compute_dtype)

        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, l, config.hidden)
        x = x + ctx @ layer["wo"].astype(compute_dtype)
        y = rms_norm(x, layer["ln2"], config.norm_eps)
        gate = y @ layer["gate"].astype(compute_dtype)
        up = y @ layer["up"].astype(compute_dtype)
        swish = gate * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
            compute_dtype
        )
        x = x + (swish * up) @ layer["down"].astype(compute_dtype)

    x = rms_norm(x, params["ln_f"], config.norm_eps)
    # HF Llama/Mistral checkpoints ship an untied lm_head; fall back to
    # weight tying (our from-scratch init) when absent
    head = params.get("lm_head", params["embed"])
    logits = jnp.einsum("blh,vh->blv", x.astype(jnp.float32), head)
    return logits, new_cache


@functools.lru_cache(maxsize=None)
def _compiled_generate(config: DecoderConfig, max_new_tokens: int,
                       temperature: float):
    """One jit for prefill + scan-decode. Static: config, step count."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def sample(logit, key):
        if temperature == 0.0:
            return jnp.argmax(logit, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logit / temperature, axis=-1
        ).astype(jnp.int32)

    def generate(params, ids, mask, rng):
        b, l = ids.shape
        positions = jnp.cumsum(mask, axis=1) - 1
        lengths = mask.sum(axis=1)  # [B]
        cache = init_kv_cache(config, b)
        kv_valid = jnp.concatenate(
            [mask, jnp.zeros((b, config.max_len - l), dtype=mask.dtype)],
            axis=1,
        )
        first_key, scan_rng = jax.random.split(rng)
        # ---- prefill: write the prompt into the cache
        logits, cache = decoder_forward(
            params, config, ids, mask, positions=positions,
            kv_cache=cache, kv_valid=kv_valid, slot_offset=0,
        )
        last_logit = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None], axis=1
        )[:, 0, :]  # [B, V]
        first = sample(last_logit, first_key)

        def step(carry, inp):
            cache, kv_valid, tok = carry
            t, key = inp
            # every row writes decode step t at slot l + t; RoPE position
            # is the row's true next position lengths + t
            kv_valid = lax.dynamic_update_slice(
                kv_valid, jnp.ones((b, 1), dtype=kv_valid.dtype), (0, l + t)
            )
            logits, cache = decoder_forward(
                params, config, tok[:, None],
                jnp.ones((b, 1), dtype=jnp.int32),
                positions=(lengths + t)[:, None],
                kv_cache=cache, kv_valid=kv_valid, slot_offset=l + t,
            )
            nxt = sample(logits[:, 0, :], key)
            return (cache, kv_valid, nxt), tok

        keys = jax.random.split(scan_rng, max_new_tokens)
        ts = jnp.arange(max_new_tokens)
        _, toks = lax.scan(step, (cache, kv_valid, first), (ts, keys))
        return toks.T  # [B, max_new_tokens]

    return jax.jit(generate, donate_argnums=())


def generate_tokens(params, config: DecoderConfig, ids, mask, *,
                    max_new_tokens: int = 16, temperature: float = 0.0,
                    seed: int = 0):
    """Greedy/temperature generation, fully on device. ids/mask: [B, L]
    (left-aligned prompts). Returns [B, max_new_tokens] int32."""
    import jax
    import jax.numpy as jnp

    l = int(np.asarray(ids).shape[1])
    if l + max_new_tokens > config.max_len:
        raise ValueError(
            f"prompt_len ({l}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"the cache budget max_len ({config.max_len}); "
            "lax.dynamic_update_slice would silently clamp and corrupt the "
            "tail cache slots"
        )
    fn = _compiled_generate(config, max_new_tokens, float(temperature))
    return np.asarray(
        fn(params, jnp.asarray(ids), jnp.asarray(mask),
           jax.random.PRNGKey(seed))
    )
