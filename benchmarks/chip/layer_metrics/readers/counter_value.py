"""The value of named counters at the window's end, not their delta:
for what happened before the window, such as set-up. spec: `scrape`
("engine" | "router"), `samples` (sample names, summed), optional
`scale`.
"""


def read(spec, ctx):
    after = ctx[spec["scrape"] + "_after"]
    if after is None or any(n not in after for n in spec["samples"]):
        return None
    return sum(after[n] for n in spec["samples"]) * float(
        spec.get("scale", 1.0))
