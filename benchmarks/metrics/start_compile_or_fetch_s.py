"""Seconds inside jax's ``compile_or_get_cached`` to the window's close
(``compile_metrics()["backend_compile_s"]``): the XLA compiles of a cold
start, the fetch and deserialisation of cached executables of a warm one."""


def read(ctx):
    return ctx["counters"].get("compile_total", {}).get("backend_compile_s")
