"""The mean of a per-request quantity over the window's requests.

spec: `field` (a function of loadgen.py applied to each record, e.g.
`request_ms`; a record for which it returns None is left out).
"""


def read(spec, ctx):
    fn = getattr(ctx["loadgen"], spec["field"])
    return ctx["loadgen"].mean(
        [v for v in map(fn, ctx["records"]) if v is not None])
