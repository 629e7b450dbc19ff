"""Roofline share of the grouped expert kernel, forward and both backward
products: the operations and bytes the rows routed to experts held require
(`kernel_work.grouped_expert_work`) over the traced seconds of the ops named
`%hg_grouped_expert*`."""

import decoder_reads
import kernel_work


def read(ctx):
    rows = decoder_reads.counter(ctx, "tokens_routed_here")
    if rows is None:
        return None
    flops, nbytes = kernel_work.grouped_expert_work(ctx["arch"], rows, ctx["window"]["batches"])
    return decoder_reads.roofline_share(ctx, "grouped_expert", flops, nbytes)
