"""1 - (union of intervals in which any operation runs on the device)
over the traced span, mean over the cell's chips, in percent."""


def read(spec, ctx):
    trace = ctx.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return (1.0 - trace["busy_s"] / trace["window_s"]) * 100.0
