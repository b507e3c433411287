"""What the fullest chip holds that is filled, beside the peak the result
line's `device` reports: the index rows written so far (set-up's and the
window's, a chip's share under dp) plus the encoder's parameters as the
program keeps them (float32)."""


from chipbench import costs


def read(ctx: dict):
    status = ctx["status_close"]
    if status is None:
        return None
    rows = (status.get("device_pipeline") or {}).get("rows")
    if rows is None:
        return None
    config, chips = ctx["cell"].config, ctx["cell"].chips
    m = config["model"]
    h = m["hidden"]
    params = (
        m["vocab_size"] * h + m["max_position_embeddings"] * h + 2 * h
        + costs.encoder_layer_params(m)
    )
    row_bytes = 4 * h + 1
    return (int(rows) * row_bytes / chips + 4 * params) / 1e9
