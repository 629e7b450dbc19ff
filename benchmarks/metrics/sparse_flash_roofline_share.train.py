"""Roofline share of the causal flash launches under the indexer's
selection, forward and backward: the operations and bytes that the window's
SELECTED (query, key) pairs require over all layers, with the bitmask read by
each launch (`count:dsa_selected_pairs`, `kernel_work_keyevl2.sparse_flash_work`),
over the traced seconds of the ops named `%hg_flash_sparse*`: what a schedule
that visits only the selected pairs could still win."""

import decoder_reads
import kernel_work_keyevl2


def read(ctx):
    chosen = decoder_reads.counter(ctx, "dsa_selected_pairs")
    if chosen is None or "indexer_num_heads" not in ctx["arch"]:
        return None
    w = ctx["window"]
    flops, nbytes = kernel_work_keyevl2.sparse_flash_work(
        ctx["arch"], chosen, w["nodes"], ctx["traffic"]["training_overrides"]["pack_node_slots"], w["batches"])
    return decoder_reads.roofline_share(ctx, "flash_sparse", flops, nbytes)
