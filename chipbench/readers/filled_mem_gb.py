"""What the fullest chip holds that is filled, beside the peak the result
line's `device` reports: the index rows written so far (set-up's and the
window's, a chip's share under dp; a row is its vector in the store's
`index_dtype` and a byte that says it is valid) plus the model's
parameters as the program keeps them (the architecture's
`resident_param_bytes`)."""


from chipbench import costs


def read(ctx: dict):
    status = ctx["status_close"]
    if status is None:
        return None
    rows = (status.get("device_pipeline") or {}).get("rows")
    if rows is None:
        return None
    config, chips = ctx["cell"].config, ctx["cell"].chips
    work, model = ctx["arch"].costs, config["model"]
    row_bytes = costs.dtype_bytes(config["store"]["index_dtype"]) * work.embed_dim(model) + 1
    return (int(rows) * row_bytes / chips + work.resident_param_bytes(model)) / 1e9
