"""Roofline share of the causal flash launches of a looped stack, forward
and backward: the operations and bytes the window's real (query, key) pairs
within documents require over every layer application, each held layer once
a pass (`count:causal_pairs` x held layers x passes, `count:tokens`;
`kernel_work_ouro.loop_flash_work`), over the traced seconds of the ops
named `%hg_flash_attention*`. A program without the loop's counters reads
nothing."""

import decoder_reads
import kernel_work_ouro


def read(ctx):
    pairs, tokens = decoder_reads.counter(ctx, "causal_pairs"), decoder_reads.counter(ctx, "tokens")
    if pairs is None or tokens is None or decoder_reads.counter(ctx, "loop_layers_held") is None:
        return None
    flops, nbytes = kernel_work_ouro.loop_flash_work(ctx["arch"], pairs, tokens)
    return decoder_reads.roofline_share(ctx, "flash_attention", flops, nbytes)
