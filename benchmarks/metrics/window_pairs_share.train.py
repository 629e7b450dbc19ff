"""Of a layer's causal (query, key) pairs within documents, the share that
lies inside the sliding window (program counters, summed over the window's
steps): what a sliding layer attends of what a full layer attends."""

import decoder_reads


def read(ctx):
    inside, every = decoder_reads.counter(ctx, "window_pairs"), decoder_reads.counter(ctx, "causal_pairs")
    return 100.0 * inside / every if inside is not None and every else None
