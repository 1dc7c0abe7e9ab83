"""Device time of one decode step's pass through the layer stack.

Today every program of the engine is called `jit_step` in the trace, so
a program cannot be picked by name. What can be picked is the layer
scan of the DECODE lanes: a `while` operation whose carried hidden state
has the shape [max_num_seqs, hidden_size] (prefill rows carry another
row count). It runs once per fused step, so its summed time over its
count is the time of one step through all layers (the lm_head and the
sampler lie outside it). spec: `lanes_flag` (the engine argument that
holds the number of decode lanes), `template` (how the carried state
appears in the operation's text).
"""


def read(spec, ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    args = ctx["config"]["engine_args"]
    lanes = int(args[args.index(spec["lanes_flag"]) + 1])
    needle = spec["template"].format(
        lanes=lanes, hidden=ctx["config"]["hidden_size"])
    total = count = 0.0
    for o in trace["ops"].values():
        if o["wrapper"] and needle in o["text"][:spec.get("within", 200)]:
            total += o["s"]
            count += o["n"]
    if not count:
        return None
    return total / count * 1e3
