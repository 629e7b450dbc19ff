"""Seconds reading executables from the compile cache to the window's
close (``compile_metrics()["cache_retrieval_s"]``; inside
``start_compile_or_fetch_s``)."""


def read(ctx):
    return ctx["counters"].get("compile_total", {}).get("cache_retrieval_s")
