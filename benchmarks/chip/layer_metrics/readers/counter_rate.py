"""Delta of named counters over the window, per second of the window.

With seconds as the counters' unit this is a share of the window's time
(`scale` 100 for percent). spec: `scrape` ("engine" | "router"),
`samples` (sample names, summed), optional `scale`.
"""


def read(spec, ctx):
    before = ctx[spec["scrape"] + "_before"]
    after = ctx[spec["scrape"] + "_after"]
    if before is None or after is None or not ctx.get("window_s"):
        return None
    if any(n not in after for n in spec["samples"]):
        return None
    delta = sum(after[n] - before.get(n, 0.0) for n in spec["samples"])
    return delta / ctx["window_s"] * float(spec.get("scale", 1.0))
