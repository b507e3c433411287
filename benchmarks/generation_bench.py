"""Generation-path benchmark (BASELINE config 4; VERDICT r4 item 2).

Drives the TP KV-cache decoder (models/decoder.py — the engine behind
xpacks.llm.llms.HFPipelineChat; reference: xpacks/llm/llms.py
HFPipelineChat:456-545, torch pipeline at batch 32) at Mistral-7B
geometry on the real chip and reports prefill tokens/s, decode tokens/s,
per-token latency, and decode MFU.

Honesty note: no pretrained 7B weights are available in this environment
(zero egress), so the weights are random bf16 at the exact Mistral-7B
architecture (7.24B params). Throughput/latency/MFU depend on shapes,
not weight values, so the numbers transfer to real checkpoints loaded
via models/hf_loader.py. The KV-cache budget (max_len) is set to the
bench's serving shape, not 4096, to fit HBM next to the 14.5 GB of
weights.

Prints ONE JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PROMPT_LEN = 512
NEW_TOKENS = 64
BATCH = 8


def _n_params(cfg) -> int:
    h, hd = cfg.hidden, cfg.head_dim
    kv_dim = cfg.kv_heads * hd
    per_layer = (
        h * h  # wq
        + h * kv_dim * 2  # wk, wv
        + h * h  # wo
        + h * cfg.mlp_dim * 2  # gate, up
        + cfg.mlp_dim * h  # down
        + 2 * h  # ln1, ln2
    )
    return cfg.vocab_size * h + h + cfg.layers * per_layer


def _bench_config(max_len: int, layers: int | None = None):
    from pathway_tpu.models.decoder import MISTRAL_7B_DECODER, DecoderConfig

    base = MISTRAL_7B_DECODER
    return DecoderConfig(
        vocab_size=base.vocab_size,
        hidden=base.hidden,
        layers=layers or base.layers,
        q_heads=base.q_heads,
        kv_heads=base.kv_heads,
        mlp_dim=base.mlp_dim,
        max_len=max_len,
        dtype="bfloat16",
    )


def _measure(cfg, label: str) -> dict:
    import jax

    from pathway_tpu.models.decoder import (
        generate_tokens,
        init_decoder_params,
    )

    params = init_decoder_params(jax.random.PRNGKey(0), cfg)
    jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])
    rng = np.random.default_rng(3)
    ids = rng.integers(
        1, cfg.vocab_size, size=(BATCH, PROMPT_LEN), dtype=np.int32
    )
    mask = np.ones_like(ids)
    one_ids = ids[:1]
    one_mask = mask[:1]

    def run(i, m, new):
        t0 = time.perf_counter()
        out = generate_tokens(params, cfg, i, m, max_new_tokens=new)
        assert out.shape[-1] == new
        return time.perf_counter() - t0

    # pay every compile (prefill+1 and prefill+NEW, both batch shapes)
    for i, m in ((ids, mask), (one_ids, one_mask)):
        run(i, m, 1)
        run(i, m, NEW_TOKENS + 1)

    def best(fn, n=3):
        return min(fn() for _ in range(n))

    t_prefill_b = best(lambda: run(ids, mask, 1))
    t_full_b = best(lambda: run(ids, mask, NEW_TOKENS + 1))
    t_prefill_1 = best(lambda: run(one_ids, one_mask, 1))
    t_full_1 = best(lambda: run(one_ids, one_mask, NEW_TOKENS + 1))

    from pathway_tpu.internals import costmodel

    decode_s_b = t_full_b - t_prefill_b
    decode_s_1 = t_full_1 - t_prefill_1
    n_params = _n_params(cfg)
    decode_tok_s = BATCH * NEW_TOKENS / decode_s_b
    # decode FLOPs/token ~= 2 * params (shared analytic model —
    # internals/costmodel.py documents the roofline count)
    flops_per_token = costmodel.decoder_flops_per_token(n_params)
    peak = _peak_flops()
    return {
        "model": label,
        "n_params_b": round(n_params / 1e9, 2),
        "batch": BATCH,
        "prompt_len": PROMPT_LEN,
        "new_tokens": NEW_TOKENS,
        "prefill_tokens_per_sec": round(
            BATCH * PROMPT_LEN / t_prefill_b
        ),
        "prefill_mfu_pct": round(
            100.0
            * (BATCH * PROMPT_LEN / t_prefill_b)
            * flops_per_token
            / peak,
            2,
        )
        if peak
        else None,
        "decode_tokens_per_sec_batch": round(decode_tok_s, 1),
        "decode_tokens_per_sec_b1": round(NEW_TOKENS / decode_s_1, 1),
        "ms_per_token_b1": round(1000.0 * decode_s_1 / NEW_TOKENS, 2),
        "decode_mfu_pct": round(
            100.0 * decode_tok_s * flops_per_token / peak, 2
        )
        if peak
        else None,
        "decode_hbm_util_pct": round(
            # decode is bandwidth-bound: each token streams the weights
            # once per batch; achieved bytes/s vs the chip's HBM BW
            100.0
            * (decode_tok_s / BATCH)
            * flops_per_token
            / _hbm_bytes_per_sec(),
            1,
        )
        if _hbm_bytes_per_sec()
        else None,
    }


def _peak_flops() -> float:
    from pathway_tpu.internals import costmodel

    return costmodel.device_peak_flops()


def _hbm_bytes_per_sec() -> float:
    from pathway_tpu.internals import costmodel

    return costmodel.device_hbm_bytes_per_sec()


def main() -> None:
    import jax

    from pathway_tpu.internals import compile_cache

    compile_cache.configure()
    device = jax.devices()[0]
    stamp = {
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
    }
    max_len = PROMPT_LEN + NEW_TOKENS + 8
    # the full depth first; shallower stacks only when it does not fit
    # beside the KV cache (which depth fits is not measured on this
    # machine — the label of the run that succeeded says which one ran)
    attempts = [
        (_bench_config(max_len), "mistral-7b-geometry (random bf16)"),
        (
            _bench_config(max_len, layers=28),
            "mistral-7b-geometry@28-layers (6.4B, random bf16)",
        ),
        (
            _bench_config(max_len, layers=16),
            "mistral-7b-geometry@16-layers (3.6B, random bf16)",
        ),
    ]
    errors = []
    for cfg, label in attempts:
        try:
            print(json.dumps({**stamp, **_measure(cfg, label)}))
            return
        except Exception as exc:  # noqa: BLE001 — OOM: try a shallower stack
            errors.append(f"{label}: {type(exc).__name__}: {exc}")
    print("\n".join(errors), file=sys.stderr)
    sys.exit(1)


if __name__ == "__main__":
    main()
