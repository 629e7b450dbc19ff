"""Programs built or fetched to the window's close
(``compile_metrics()["programs"]``: backend-compile events); where the
program does not count them, its cache hits + misses, which leave out the
programs that never ask the cache."""


def read(ctx):
    c = ctx["counters"].get("compile_total")
    if c is None:
        return None
    if "programs" in c:
        return c["programs"]
    if "cache_hits" in c and "cache_misses" in c:
        return c["cache_hits"] + c["cache_misses"]
    return None
