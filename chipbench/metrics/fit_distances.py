"""Distance computations per fit, as the fit itself counts them
(``FitResult.distances``, the paper's cost unit): the mean over the window."""


def read(ctx):
    results = ctx["window"].get("results")
    if not results:
        return None
    return sum(r.distances for r in results) / len(results)
