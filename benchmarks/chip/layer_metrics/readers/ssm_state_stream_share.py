"""Least time the decode lanes' recurrent-state updates could take, over
the time the update kernel took.

A state-space layer's one-token update reads a lane's state and writes
it back: 2 x (the family's `state_bytes_per_seq` / its state-space
layers) a lane and layer. Only lanes that hold a sequence do it (the
kernel skips a lane that is nobody's), so the bytes follow the program's
counter of those updates (`samples`: decode lanes that hold a sequence x
fused steps x state-space layers), not the program's shape: shape x
calls read 739% where one lane in twelve was live (my chip run, PR 45).
Numerator: the counter's delta over the window times those bytes, over
the chip's peak memory bandwidth, as a share of the measured window.
Denominator: the summed device time of the kernel in the trace (`ops`,
as `trace_op_share`), as a share of the traced span. Each is a rate over
its own steady span, as `attn_kv_stream_share` is and for its reason:
the harness reads counters at the window's edges only. In percent.

Left out of the bytes: the rows' x, B, C, dt and y (a thousandth of the
state), and what a skipped lane still costs (a grid step, and slot 0's
copy where the lane before it was live); a lane frozen by a device stop
is counted to its round's end though the kernel skips it (about 2% of
the updates). spec: `ops`, `samples`, `scrape`.
"""

import re


def read(spec, ctx):
    trace = ctx.get("trace")
    family = ctx["family"]
    before = ctx.get(spec["scrape"] + "_before")
    after = ctx.get(spec["scrape"] + "_after")
    if not trace or before is None or after is None or not hasattr(
            family, "state_bytes_per_seq"):
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
    config = ctx["config"]
    updates = sum(after[n] - before.get(n, 0.0) for n in spec["samples"])
    layers = config["hybrid_override_pattern"].count("M")
    nbytes = updates * 2 * family.state_bytes_per_seq(config) / layers
    least_s = nbytes / ctx["chips"] / ctx["peak"]["hbm_bytes_per_s"]
    return ((least_s / ctx["window_s"])
            / (kernel_s / trace["window_s"]) * 100.0)
