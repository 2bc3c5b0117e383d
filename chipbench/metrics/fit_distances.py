"""Distance computations per fit, as the fit itself counts them
(``FitResult.distances``, the paper's cost unit): the mean over the
window's first ``quality_fits`` fits, the same fits in every run."""


def read(ctx):
    win = ctx["window"]
    results = win["results"][:win["quality_fits"]]
    if not results:
        return None
    return sum(r.distances for r in results) / len(results)
