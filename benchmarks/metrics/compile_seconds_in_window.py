"""Seconds of tracing, lowering and compiling or fetching inside the window
(``compile_metrics()``, after minus before): expected 0 beside
``compiles_in_window`` 0. None where the program counts no tracing or
lowering seconds."""


def read(ctx):
    c = ctx["counters"].get("compile", {})
    keys = ("trace_s", "lower_s", "backend_compile_s")
    if any(k not in c for k in keys):
        return None
    return sum(c[k] for k in keys)
