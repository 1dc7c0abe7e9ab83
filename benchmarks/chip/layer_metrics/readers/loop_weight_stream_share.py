"""Share of the step programs' time that the re-read layer weights must
take.

Numerator: the passes of the layer stack the dispatched programs ran in
the window (the program's counter: their forwards x the model's loop
count), times the bytes of weights ONE pass reads (`layer_stack_bytes`
of the cell's family), over the cell's chips, over the chip's peak
memory bandwidth (peaks.json): seconds of pure weight streaming, as a
share of the measured window. Denominator: the summed device time of
the step programs in the trace (`modules`, on the `XLA Modules` line),
as a share of the traced span. Each is a rate over its own steady span.
In percent.

Left out of the bytes, so this is below a true roofline share and is
not named one: the KV cache (`attn_kv_stream_share.serve` counts it, at
the family's bytes a token over all passes), the head, the embedding
rows. A prefill forward counts as one pass-set though its rows make it
compute-bound, and a round that a device stop ends early is counted to
its last step: both are small beside the decode steps here. spec:
`passes` (the counter's sample), `modules` (as `trace_module_ms`),
`scrape`.
"""

import re


def read(spec, ctx):
    trace = ctx.get("trace")
    before = ctx.get(spec["scrape"] + "_before")
    after = ctx.get(spec["scrape"] + "_after")
    if not trace or before is None or after is None:
        return None
    if spec["passes"] not in after:
        return None
    if not trace["window_s"] or not ctx["window_s"]:
        return None
    pat = re.compile(spec["modules"])
    program_s = sum(m["total_s"] for name, m in
                    trace.get("modules", {}).items() if pat.search(name))
    if not program_s:
        return None
    passes = after[spec["passes"]] - before.get(spec["passes"], 0.0)
    nbytes = passes * ctx["family"].layer_stack_bytes(ctx["config"])
    least_s = nbytes / ctx["chips"] / ctx["peak"]["hbm_bytes_per_s"]
    return ((least_s / ctx["window_s"])
            / (program_s / trace["window_s"]) * 100.0)
