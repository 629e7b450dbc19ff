"""Rows computed on this chip a (token, expert layer) in the AFMOE stack: a
token sends its 8 choices to all 128 experts and 0 to 8 of them are held here
(program counters, summed over the window); 8 x 8 / 128 = 0.5 when balanced."""

import decoder_reads


def read(ctx):
    rows, every = decoder_reads.counter(ctx, "expert_rows_here"), decoder_reads.counter(ctx, "tokens")
    if rows is None or not every or "layer_types" not in ctx["arch"]:
        return None
    return rows / every
