"""Device-to-host reads per fit, as the fit itself counts them
(``FitResult.metadata["counters"]["host_syncs"]``, ``repro.obs.pull``): the
mean over the window's first ``quality_fits`` fits, the same fits in every
run. None where the program keeps no such counter."""


def read(ctx):
    win = ctx["window"]
    results = win["results"][:win["quality_fits"]]
    counts = [(r.metadata.get("counters") or {}).get("host_syncs") for r in results]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts)
