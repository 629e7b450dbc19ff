"""Device time of the grouped expert product's launches on a top-k layout
(Mosaic ops named `%hg_grouped_expert*`) over device busy time."""

import span_reads


def read(ctx):
    return span_reads.kernel_share_of_busy(ctx, "grouped_expert")
