"""Device time of the Mosaic ops named `%hg_grouped_expert*` (forward and both
backward products) over device busy time, in the traced span."""

import span_reads


def read(ctx):
    return span_reads.kernel_share_of_busy(ctx, "grouped_expert")
