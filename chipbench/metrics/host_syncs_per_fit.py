"""Device-to-host reads per fit, as the fit itself counts them
(``FitResult.metadata["counters"]["host_syncs"]``, ``repro.obs.pull``): the
mean over the window. None where the program keeps no such counter."""


def read(ctx):
    results = ctx["window"].get("results")
    counts = [(r.metadata.get("counters") or {}).get("host_syncs") for r in results or []]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts)
