"""Device time of the Mosaic ops named `%hg_sorted_segment*` over device
busy time, in the traced span."""

import span_reads


def read(ctx):
    return span_reads.kernel_share_of_busy(ctx, "sorted_segment")
