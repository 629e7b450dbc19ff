"""Roofline share of the causal flash kernel, forward and backward: the
operations and bytes the window's real (query, key) pairs within documents
require (`kernel_work.flash_attention_work`) over the traced seconds of the
ops named `%hg_flash_attention*`."""

import decoder_reads
import kernel_work


def read(ctx):
    pairs, tokens = decoder_reads.counter(ctx, "causal_pairs"), decoder_reads.counter(ctx, "tokens")
    if pairs is None or tokens is None:
        return None
    layers = int(ctx["arch"]["num_conv_layers"])
    flops, nbytes = kernel_work.flash_attention_work(ctx["arch"], pairs * layers, tokens)
    return decoder_reads.roofline_share(ctx, "flash_attention", flops, nbytes)
