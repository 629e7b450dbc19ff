"""Programs compiled or fetched from the compile cache inside the window
(``compile_metrics()`` hits + misses, after minus before): expected 0."""


def read(ctx):
    c = ctx["counters"]["compile"]
    return c["cache_hits"] + c["cache_misses"]
