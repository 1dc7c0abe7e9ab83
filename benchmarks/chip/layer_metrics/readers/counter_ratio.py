"""Delta of named counters over the window, optionally over another.

spec: `scrape` ("engine" | "router"), `numerator` (sample names, summed),
optional `denominator` (sample names, summed), optional `scale`.
Histograms are read as their `_sum` and `_count` samples: a mean over
the window is delta-sum over delta-count, never a bucket quantile.
"""


def read(spec, ctx):
    before = ctx[spec["scrape"] + "_before"]
    after = ctx[spec["scrape"] + "_after"]
    if before is None or after is None:
        return None

    def delta(names):
        if any(n not in after for n in names):
            return None
        return sum(after[n] - before.get(n, 0.0) for n in names)

    num = delta(spec["numerator"])
    if num is None:
        return None
    scale = float(spec.get("scale", 1.0))
    if "denominator" not in spec:
        return num * scale
    den = delta(spec["denominator"])
    if not den:
        return None
    return num / den * scale
