"""Seconds the program spent lowering jaxprs to MLIR modules, the Mosaic
kernels' lowering included (``compile_metrics()["lower_s"]``), to the
window's close. None where the program does not count them."""


def read(ctx):
    return ctx["counters"].get("compile_total", {}).get("lower_s")
