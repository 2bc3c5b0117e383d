"""Wall time of the program's ``bwkm.init`` spans (paper Algorithms 2-4),
per traced fit, on the trace's clock (``chipbench.program_trace``). None
where the program opens no ``bwkm.fit`` span."""

from chipbench import program_trace


def read(ctx):
    result = program_trace.for_run(ctx)
    if result is None:
        return None
    return program_trace.per_fit_ms(
        result, lambda f: f["rows"].get("bwkm.init", {}).get("wall_ns", 0))
