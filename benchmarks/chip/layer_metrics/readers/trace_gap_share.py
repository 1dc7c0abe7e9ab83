"""Share of the device's idle time whose gaps carry a matching name.

`trace["idle_gaps"]` (trace_reduce.py) lists the idle gaps of chip 0
summed by the innermost host event over each gap's midpoint: a phase
span of the program (`engine.fetch`, `engine.apply`, ...), an event of
the runtime, or `unattributed` where no host event covers it. This is
the seconds of the rows whose name matches over the seconds of all rows
(the reducer keeps the ten largest), in percent; 0 where none matches.
spec: `names` (regular expression, searched in the row's name).
"""

import re


def read(spec, ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("chips"):
        return None
    rows = trace["idle_gaps"]
    total = sum(s for _, s in rows)
    if not total:
        return 0.0
    pat = re.compile(spec["names"])
    return sum(s for name, s in rows if pat.search(name)) / total * 100.0
