"""Roofline share of the sparse attention's indexer, both launches: the
operations and bytes of its scores on the window's causal pairs and of its
loss and gradient on the selected pairs (`count:causal_pairs`,
`count:dsa_selected_pairs`, `kernel_work_keyevl2.indexer_work`) over the
traced seconds of the ops named `%hg_dsa_indexer*`."""

import decoder_reads
import kernel_work_keyevl2


def read(ctx):
    every, chosen = decoder_reads.counter(ctx, "causal_pairs"), decoder_reads.counter(ctx, "dsa_selected_pairs")
    if every is None or chosen is None or "indexer_num_heads" not in ctx["arch"]:
        return None
    w = ctx["window"]
    flops, nbytes = kernel_work_keyevl2.indexer_work(
        ctx["arch"], every, chosen, w["nodes"], ctx["traffic"]["training_overrides"]["pack_node_slots"],
        w["batches"])
    return decoder_reads.roofline_share(ctx, "dsa_indexer", flops, nbytes)
