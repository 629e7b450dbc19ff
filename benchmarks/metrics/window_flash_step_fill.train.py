"""`flash_step_fill.train` for a SLIDING layer's launch (`hg_flash_window`):
share of its scheduled steps that visit a tile of a query block's key window
under the sliding bound (program counters `count:flash_window_tiles_visited`
over `count:flash_window_steps_scheduled`, one block's forward launch, one
head, summed over the window's steps): 100 where the window's loop runs
inside the kernel."""

import decoder_reads


def read(ctx):
    visited = decoder_reads.counter(ctx, "flash_window_tiles_visited")
    scheduled = decoder_reads.counter(ctx, "flash_window_steps_scheduled")
    return 100.0 * visited / scheduled if visited is not None and scheduled else None
