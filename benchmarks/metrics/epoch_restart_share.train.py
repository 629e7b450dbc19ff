"""Share of the window the main loop spent in `epoch_restart`: from before
`iter(loader)` to the epoch's first batch in hand, once an epoch, while the
drained device waits."""

import span_reads


def read(ctx):
    return span_reads.region_share(ctx, "epoch_restart")
