"""A percentile of a per-request quantity of the window's requests.

spec: `field` (a function of loadgen.py applied to each record, e.g.
`late_ms`; one that returns a list, e.g. `token_gaps_ms`, gives all its
values), `percentile`.
"""


def read(spec, ctx):
    fn = getattr(ctx["loadgen"], spec["field"])
    values = []
    for rec in ctx["records"]:
        v = fn(rec)
        if isinstance(v, list):
            values.extend(v)
        elif v is not None:
            values.append(v)
    return ctx["loadgen"].percentile(values, float(spec["percentile"]))
