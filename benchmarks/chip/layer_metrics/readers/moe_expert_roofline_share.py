"""Least time the routed expert layers' work could take, over the time
their operations took.

Numerator: the larger of two floors over the window. Bytes: the local
experts that had at least one row, summed over layers and fused steps
(the program's counter, computed on the device), times the bytes of one
expert's weights (`expert_bytes` of the cell's family), over the chip's
peak memory bandwidth. Operations: the (row, expert) pairs whose expert
is held here (the program's counter), times the operations of one pair
(`expert_flops_per_row`), over the chip's peak bf16 rate. Both over the
cell's chips, as a share of the measured window. Denominator: the summed
device time of the expert operations in the trace (`ops`, as
`trace_op_share`), as a share of the traced span. Each is a rate over its
own steady span. In percent.

Left out of the bytes: the rows themselves, the router, the sort and the
combine (they are not in the expert operations either, or are small
beside an expert's 50 MB). A form that reads every local expert whether
it has rows or not reads more than is counted here and scores lower for
it. spec: `ops`, `active_experts`, `local_rows` (sample names), `scrape`.
"""

import re


def read(spec, ctx):
    trace = ctx.get("trace")
    before = ctx.get(spec["scrape"] + "_before")
    after = ctx.get(spec["scrape"] + "_after")
    family = ctx["family"]
    if not trace or before is None or after is None:
        return None
    names = (spec["active_experts"], spec["local_rows"])
    if any(n not in after for n in names):
        return None
    if not all(hasattr(family, f)
               for f in ("expert_bytes", "expert_flops_per_row")):
        return None
    if not trace["window_s"] or not ctx["window_s"]:
        return None
    pat = re.compile(spec["ops"])
    op_s = sum(o["s"] for o in trace["ops"].values()
               if not o["wrapper"] and pat.search(o["text"]))
    if not op_s:
        return None
    active, rows = (after[n] - before.get(n, 0.0) for n in names)
    peak = ctx["peak"]
    least_s = max(
        active * family.expert_bytes(ctx["config"])
        / peak["hbm_bytes_per_s"],
        rows * family.expert_flops_per_row(ctx["config"])
        / peak["bf16_flops_per_s"],
    ) / ctx["chips"]
    return ((least_s / ctx["window_s"])
            / (op_s / trace["window_s"]) * 100.0)
