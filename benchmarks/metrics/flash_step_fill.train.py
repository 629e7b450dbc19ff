"""Share of the causal flash launch's scheduled steps that visit a tile of a
query block's key window (program counters, one block's forward launch, one
head, summed over the window's steps): 100 where the window's loop runs
inside the kernel, q_blocks x k_windows under the grid schedule."""

import decoder_reads


def read(ctx):
    visited = decoder_reads.counter(ctx, "flash_tiles_visited")
    scheduled = decoder_reads.counter(ctx, "flash_steps_scheduled")
    return 100.0 * visited / scheduled if visited is not None and scheduled else None
