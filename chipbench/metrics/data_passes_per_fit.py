"""Operations that read every row of the data, per fit, as the fit itself
counts them (``FitResult.metadata["counters"]["data_passes"]``,
``repro.obs.data_pass``): the mean over the window's first ``quality_fits``
fits, the same fits in every run. None where the program keeps no such
counter."""


def read(ctx):
    win = ctx["window"]
    results = win["results"][:win["quality_fits"]]
    counts = [(r.metadata.get("counters") or {}).get("data_passes") for r in results]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts)
