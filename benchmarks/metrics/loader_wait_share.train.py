"""Share of the window the training loop spent inside its ``dataload``
region (waiting for the next batch): the program's own span, summed over the
whole window, over the window's length."""


def read(ctx):
    wait = ctx["counters"]["regions"].get("dataload")
    if wait is None or ctx["window"]["seconds"] <= 0:
        return None
    return 100.0 * wait / ctx["window"]["seconds"]
