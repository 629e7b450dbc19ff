"""Share of the window's (token, layer) routings whose expert is held on this
chip (program counters): the rest get nothing from the expert sublayer."""

import decoder_reads


def read(ctx):
    here, every = decoder_reads.counter(ctx, "tokens_routed_here"), decoder_reads.counter(ctx, "tokens")
    return 100.0 * here / every if here is not None and every else None
