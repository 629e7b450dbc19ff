"""Device time of the Mosaic ops named `%hg_grouped_expert*` (the grouped
expert product of the AFMOE stack's expert layers, forward and backward) over
device busy time, in the traced span."""

import span_reads


def read(ctx):
    if "layer_types" not in ctx["arch"]:
        return None
    return span_reads.kernel_share_of_busy(ctx, "grouped_expert")
