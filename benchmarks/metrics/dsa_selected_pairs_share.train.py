"""Of a layer's causal (query, key) pairs within documents, the share the
indexer selects (program counters, summed over the window's steps): what the
sparse launches attend of what dense causal attention would."""

import decoder_reads


def read(ctx):
    chosen, every = decoder_reads.counter(ctx, "dsa_selected_pairs"), decoder_reads.counter(ctx, "causal_pairs")
    return 100.0 * chosen / every if chosen is not None and every else None
