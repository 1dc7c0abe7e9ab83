"""Device time of one pass of a looped stack in a decode round.

A looped model's decode round is a `while` over fused steps that holds a
`while` over PASSES that holds the `while` over LAYERS
(`models/llama.forward`); one execution of the innermost is one pass of
the layer stack over the decode lanes (the pass's closing norm and gate
lie outside it: one row-wise operation each). Both inner loops carry the
lanes' hidden state, `[max_num_seqs, hidden_size]`, so the shape alone
(`trace_decode_scan_step_ms`) cannot tell them apart. What can: a loop
over passes takes `passes` times as long an execution as the loop over
layers inside it. Of the `while` operations that carry that state, the
ones whose mean execution is under sqrt(passes) times the shortest mean
are loops over layers; their summed time over their count is the time
of one pass. The reducer keeps sums and counts by operation, no single
events, so this is a mean, not a median.

Nothing to read where the configuration has no loop count or the trace
no such `while` (a program that runs its stack once). spec:
`lanes_flag`, `template`, `within` (as `trace_decode_scan_step_ms`),
`passes_key` (the configuration's loop count).
"""


def read(spec, ctx):
    trace = ctx.get("trace")
    passes = int(ctx["config"].get(spec["passes_key"]) or 1)
    if not trace or passes < 2:
        return None
    args = ctx["config"]["engine_args"]
    lanes = int(args[args.index(spec["lanes_flag"]) + 1])
    needle = spec["template"].format(
        lanes=lanes, hidden=ctx["config"]["hidden_size"])
    loops = [(o["s"], o["n"]) for o in trace["ops"].values()
             if o["wrapper"] and o["n"]
             and needle in o["text"][:spec.get("within", 200)]]
    if not loops:
        return None
    shortest = min(s / n for s, n in loops)
    inner = [(s, n) for s, n in loops if s / n < shortest * passes ** 0.5]
    return sum(s for s, _ in inner) / sum(n for _, n in inner) * 1e3
