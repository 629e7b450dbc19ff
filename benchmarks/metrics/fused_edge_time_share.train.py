"""Device time of the Mosaic ops named `%hg_fused_edge*` over device busy
time, in the traced span."""

import span_reads


def read(ctx):
    return span_reads.kernel_share_of_busy(ctx, "fused_edge")
