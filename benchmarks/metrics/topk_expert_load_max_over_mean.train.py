"""Largest load of a held expert over the mean load of the held experts under
top-k routing, both summed over expert layers and steps of the window (program
counters): 1 is even."""

import decoder_reads


def read(ctx):
    if decoder_reads.counter(ctx, "expert_rows_here") is None:
        return None
    top, mean = decoder_reads.counter(ctx, "expert_load_max"), decoder_reads.counter(ctx, "expert_load_mean")
    return top / mean if top is not None and mean else None
