"""`flash_step_fill.train` for the FULL layers of a stack that also has sliding
ones: share of the causal launch's scheduled steps that visit a tile of a
query block's key window (program counters `count:flash_tiles_visited` over
`count:flash_steps_scheduled`, one block's forward launch, one head, summed
over the window's steps): 100 where the window's loop runs inside the
kernel."""

import decoder_reads


def read(ctx):
    if "layer_types" not in ctx["arch"]:
        return None
    visited = decoder_reads.counter(ctx, "flash_tiles_visited")
    scheduled = decoder_reads.counter(ctx, "flash_steps_scheduled")
    return 100.0 * visited / scheduled if visited is not None and scheduled else None
