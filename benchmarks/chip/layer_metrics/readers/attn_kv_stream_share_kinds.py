"""`attn_kv_stream_share` for a family whose attention layers read
different numbers of tokens: least time the attention kernels' KV reads
could take, over the time the kernels took.

Numerator: for each attention kind, the context tokens a LAYER of that
kind had to read (the program's counter per kind: each decode lane's
context at each fused step and each prefill chunk's end context, the
window kind cut to its window), times the KV bytes a token takes in all
the layers of that kind (`kv_bytes_per_token_by_kind` of the cell's
family), summed over the kinds, over the cell's chips, over the chip's
peak memory bandwidth: seconds of pure KV streaming, as a share of the
measured window. Denominator: the summed device time of the attention
kernels in the trace, as a share of the traced span. In percent.

What is left out, and why this is below a roofline share and not named
one, is what `attn_kv_stream_share` says of itself. One more here: the
kernels walk whole KV blocks of 128 keys, so a window of 128 that does
not start on a block boundary reads up to two blocks. spec: `ops`,
`samples` (kind -> the counter's sample name), `scrape`.
"""

import re


def read(spec, ctx):
    trace = ctx.get("trace")
    before = ctx.get(spec["scrape"] + "_before")
    after = ctx.get(spec["scrape"] + "_after")
    family = ctx["family"]
    if not trace or before is None or after is None:
        return None
    if any(n not in after for n in spec["samples"].values()):
        return None
    if not hasattr(family, "kv_bytes_per_token_by_kind"):
        return None
    if not trace["window_s"] or not ctx["window_s"]:
        return None
    pat = re.compile(spec["ops"])
    kernel_s = sum(o["s"] for o in trace["ops"].values()
                   if not o["wrapper"] and pat.search(o["text"]))
    if not kernel_s:
        return None
    per_token = family.kv_bytes_per_token_by_kind(ctx["config"])
    nbytes = sum(
        (after[name] - before.get(name, 0.0)) * per_token[kind]
        for kind, name in spec["samples"].items())
    if not nbytes:
        return None
    least_s = nbytes / ctx["chips"] / ctx["peak"]["hbm_bytes_per_s"]
    return ((least_s / ctx["window_s"])
            / (kernel_s / trace["window_s"]) * 100.0)
