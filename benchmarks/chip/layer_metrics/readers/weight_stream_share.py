"""Share of the weight-streaming bound that a decode step reaches.

The bytes of layer weights one decode step must read (`layer_stack_bytes`
of the cell's family, `ctx["family"]`; the chip's share of them under
tensor parallelism) over the chip's peak memory bandwidth (peaks.json),
over the measured time of one step's pass through the layer stack
(`step_metric`). The lm_head lies outside that pass and is left out of
both; KV-cache bytes are left out of the bytes, so this is below a true
roofline share and is named for what it is. spec: `step_metric`.
"""


def read(spec, ctx):
    step_ms = ctx["read"](spec["step_metric"])
    if not step_ms:
        return None
    nbytes = ctx["family"].layer_stack_bytes(ctx["config"]) / ctx["chips"]
    least_s = nbytes / ctx["peak"]["hbm_bytes_per_s"]
    return least_s / (step_ms / 1e3) * 100.0
