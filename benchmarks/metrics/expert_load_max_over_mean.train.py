"""Largest load of a held expert over the mean load of the held experts, both
summed over layers and steps of the window (program counters): 1 is even."""

import decoder_reads


def read(ctx):
    top, mean = decoder_reads.counter(ctx, "expert_load_max"), decoder_reads.counter(ctx, "expert_load_mean")
    return top / mean if top is not None and mean else None
