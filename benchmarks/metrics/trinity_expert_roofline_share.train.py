"""Roofline share of the grouped expert product in the AFMOE stack, forward
and backward: the operations and bytes of the rows really computed here
(`count:expert_rows_here`) with the held experts' weights once a pass over
the EXPERT layers (`kernel_work_afmoe.expert_work`) over the traced seconds of
the ops named `%hg_grouped_expert*`."""

import decoder_reads
import kernel_work_afmoe


def read(ctx):
    rows = decoder_reads.counter(ctx, "expert_rows_here")
    if rows is None or "layer_types" not in ctx["arch"]:
        return None
    flops, nbytes = kernel_work_afmoe.expert_work(ctx["arch"], rows, ctx["window"]["batches"])
    return decoder_reads.roofline_share(ctx, "grouped_expert", flops, nbytes)
