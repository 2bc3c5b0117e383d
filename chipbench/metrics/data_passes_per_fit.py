"""Operations that read every row of the data, per fit, as the fit itself
counts them (``FitResult.metadata["counters"]["data_passes"]``,
``repro.obs.data_pass``): the mean over the window. None where the program
keeps no such counter."""


def read(ctx):
    results = ctx["window"].get("results")
    counts = [(r.metadata.get("counters") or {}).get("data_passes") for r in results or []]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts)
