"""Least time the attention kernels' KV reads could take, over the time
the kernels took.

Numerator: the context tokens the dispatched rounds' attention calls had
to read once (the program's counter: each decode lane's context at each
fused step, each prefill chunk's end context), times the KV bytes of a
token (the cell's family, `ctx["family"]`: one constant a
configuration, so a family whose layers read different numbers of
tokens brings a reader of its own), over the cell's chips, over the
chip's peak memory bandwidth (peaks.json): seconds of pure KV
streaming, as a share of the measured window. Denominator: the summed device time of the attention
kernels in the trace, as a share of the traced span. Each is a rate over
its own steady span, so the 5 s trace and the whole window may differ in
length. In percent.

Left out of the bytes, so this is below a true roofline share and is not
named one: the weights of the q/k/v/o projections (they are not in these
kernels), the query rows and the output rows. The kernels read whole
pages and the counter counts a lane frozen by a device stop to its
round's end: a few percent either way. spec: `ops` (the kernels, as
`trace_op_share`), `samples` (the counter's sum), `scrape`.
"""

import re


def read(spec, ctx):
    trace = ctx.get("trace")
    before = ctx.get(spec["scrape"] + "_before")
    after = ctx.get(spec["scrape"] + "_after")
    if not trace or before is None or after is None:
        return None
    if any(n not in after for n in spec["samples"]):
        return None
    if not trace["window_s"] or not ctx["window_s"]:
        return None
    pat = re.compile(spec["ops"])
    kernel_s = sum(o["s"] for o in trace["ops"].values()
                   if not o["wrapper"] and pat.search(o["text"]))
    if not kernel_s:
        return None
    tokens = sum(after[n] - before.get(n, 0.0) for n in spec["samples"])
    nbytes = tokens * ctx["family"].kv_bytes_per_token(ctx["config"])
    least_s = nbytes / ctx["chips"] / ctx["peak"]["hbm_bytes_per_s"]
    return ((least_s / ctx["window_s"])
            / (kernel_s / trace["window_s"]) * 100.0)
