"""Device time of the Mosaic ops named `%hg_dsa_indexer*` (the sparse
attention's indexer: the selection launch and the launch of its loss and
gradient) over device busy time, in the traced span."""

import span_reads


def read(ctx):
    return span_reads.kernel_share_of_busy(ctx, "dsa_indexer")
