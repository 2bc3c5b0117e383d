"""Device idle time charged to the program's ``bwkm.init`` span and its
children (the host loop of Algorithms 2-4), per traced fit
(``chipbench.program_trace``). None where the program opens no
``bwkm.fit`` span."""

from chipbench import program_trace


def read(ctx):
    result = program_trace.for_run(ctx)
    if result is None:
        return None
    return program_trace.per_fit_ms(result, lambda f: f["idle_under_ns"].get("bwkm.init", 0))
