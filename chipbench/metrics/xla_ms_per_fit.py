"""Device time of XLA operations that are not Mosaic kernels, per traced fit: the
partition ops, routing and statistics over all rows (trace)."""


def read(ctx):
    trace, results = ctx["trace"], ctx["window"].get("results")
    if trace is None or not results or trace["busy_s"] <= 0:
        return None
    return trace["xla_s"] / ctx["traced_fits"] * 1e3
