"""Share of a decoder stack's attention blocks whose causal flash launch keeps
its residuals (``o`` and a row's ``lse``) across the layer's
rematerialisation, so that the forward kernel runs once a step and not twice
(program counters `count:flash_blocks_saved` over `count:flash_blocks`, summed
over the window's steps): 100 where every layer keeps them, 0 under a bare
remat; a program without the counters reads nothing."""

import decoder_reads


def read(ctx):
    saved = decoder_reads.counter(ctx, "flash_blocks_saved")
    blocks = decoder_reads.counter(ctx, "flash_blocks")
    return 100.0 * saved / blocks if saved is not None and blocks else None
