"""Summed device time of the leaf operations whose HLO text matches,
over the device's busy time, in percent. spec: `ops` (regular
expression, searched in the operation's text as the trace gives it)."""

import re


def read(spec, ctx):
    trace = ctx.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    pat = re.compile(spec["ops"])
    hit = sum(o["s"] for o in trace["ops"].values()
              if not o["wrapper"] and pat.search(o["text"]))
    return hit / trace["busy_s"] * 100.0
