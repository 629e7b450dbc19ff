"""Seconds the program spent tracing Python to jaxprs, from its listeners'
installation to the window's close (``compile_metrics()["trace_s"]``: the
outermost trace of each program, which encloses those of the functions it
calls). None where the program does not count them."""


def read(ctx):
    return ctx["counters"].get("compile_total", {}).get("trace_s")
