"""Device time of the Mosaic ops named `%hg_flash_sparse*` (the causal flash
launches under the indexer's selection, forward and the tiled backward) over
device busy time, in the traced span."""

import span_reads


def read(ctx):
    return span_reads.kernel_share_of_busy(ctx, "flash_sparse")
