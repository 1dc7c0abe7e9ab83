"""Mean device time of one execution of the programs whose name matches.

`trace["modules"]` (trace_reduce.py) holds, per program on the trace's
`XLA Modules` line, its executions and their summed time (mean over
chips). The program gives each step program the name of its kind
(`jit_decode_multi`, `jit_ragged_rows`, ...), so a round is picked by
name and not by the shape of an operation inside it. Nothing matches
where every program is still called `jit_step`. spec: `modules`
(regular expression, searched in the program's name).
"""

import re


def read(spec, ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    pat = re.compile(spec["modules"])
    hit = [m for name, m in trace.get("modules", {}).items()
           if pat.search(name)]
    count = sum(m["count"] for m in hit)
    if not count:
        return None
    return sum(m["total_s"] for m in hit) / count * 1e3
