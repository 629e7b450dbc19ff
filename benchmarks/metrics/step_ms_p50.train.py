"""Median interval between the ends of consecutive step programs on the
device (the trace's modules line)."""


def read(ctx):
    return ctx["trace"]["step_ms_p50"] if ctx["trace"] else None
