"""Least time ONE call of the decode lanes' state-update kernel could
take, over the time a call took.

A call updates the lanes that hold a sequence (the kernel skips a lane
that is nobody's) in one state layer; each reads its state and writes it
back: the family's `state_update_bytes_per_lane`. Numerator: the mean
live lanes a call over the window, the program's counter of lane-layer
updates (`samples`) over its counter of the kernel's calls (`calls`),
times those bytes, over the chip's peak memory bandwidth. Denominator:
the kernel's summed device time in the trace over its number of events
there (`ops`, as `trace_op_share`). PER CALL on both sides, so that no
span enters: `ssm_state_stream_share` divides a rate over the window by
a rate over the trace, and an idle stretch of the trace reads it over
100% (PERF.md, Open questions 6). The live lanes a call are the
window's mean, the time a call the trace's: the trace lies inside the
window, under the same offered load. In percent.

Left out of the bytes: the rows' q, k, v, decay and y and the
convolutions' tail (2% of the state), and what a skipped lane still
costs (a grid step); a lane frozen by a device stop is counted to its
round's end though the kernel skips it. spec: `ops`, `samples`, `calls`,
`scrape`.
"""

import re


def read(spec, ctx):
    trace = ctx.get("trace")
    family = ctx["family"]
    before = ctx.get(spec["scrape"] + "_before")
    after = ctx.get(spec["scrape"] + "_after")
    if not trace or before is None or after is None or not hasattr(
            family, "state_update_bytes_per_lane"):
        return None
    names = spec["samples"] + spec["calls"]
    if any(n not in after for n in names):
        return None

    def delta(ns):
        return sum(after[n] - before.get(n, 0.0) for n in ns)

    calls = delta(spec["calls"])
    pat = re.compile(spec["ops"])
    hit = [o for o in trace["ops"].values()
           if not o["wrapper"] and pat.search(o["text"])]
    kernel_s, kernel_n = sum(o["s"] for o in hit), sum(o["n"] for o in hit)
    if not calls or not kernel_s or not kernel_n:
        return None
    lanes_a_call = delta(spec["samples"]) / calls
    nbytes = lanes_a_call * family.state_update_bytes_per_lane(ctx["config"])
    least_s = nbytes / ctx["peak"]["hbm_bytes_per_s"]
    return least_s / (kernel_s / kernel_n) * 100.0
