"""Rows computed on this chip a (token, expert layer): a token sends its k
choices to all experts and 0 to k of them are held here (program counters,
summed over the window); k x held / experts when balanced."""

import decoder_reads


def read(ctx):
    rows, every = decoder_reads.counter(ctx, "expert_rows_here"), decoder_reads.counter(ctx, "tokens")
    return rows / every if rows is not None and every else None
