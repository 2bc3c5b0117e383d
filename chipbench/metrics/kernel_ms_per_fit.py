"""Device time of Mosaic kernels (``tpu_custom_call``), per traced fit (trace).

Every fit runs the fused kernels on a TPU, so a trace with device time and
no Mosaic kernel in it means the reduction no longer recognises them: that
raises, rather than reading 0 here and the kernels' time under XLA.
"""


def read(ctx):
    trace, results = ctx["trace"], ctx["window"].get("results")
    if trace is None or not results:
        return None
    if trace["busy_s"] > 0 and trace["mosaic_s"] <= 0:
        raise ValueError("the traced fits ran no recognised Mosaic kernel: "
                         f"top device ops {trace['top_ops']!r}")
    return trace["mosaic_s"] / ctx["traced_fits"] * 1e3
