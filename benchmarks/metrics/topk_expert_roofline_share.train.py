"""Roofline share of the grouped expert product on a top-k layout, forward and
backward: the operations and bytes of the rows really computed here
(`count:expert_rows_here`, `kernel_work_joyai.topk_expert_work`) over the
traced seconds of the ops named `%hg_grouped_expert*`."""

import decoder_reads
import kernel_work_joyai


def read(ctx):
    rows = decoder_reads.counter(ctx, "expert_rows_here")
    if rows is None:
        return None
    flops, nbytes = kernel_work_joyai.topk_expert_work(ctx["arch"], rows, ctx["window"]["batches"])
    return decoder_reads.roofline_share(ctx, "grouped_expert", flops, nbytes)
