"""Roofline share of the causal flash kernel's FULL launches in a stack that
also has sliding layers, forward and backward: the operations and bytes the
window's real causal (query, key) pairs require over the full layers
(`count:causal_pairs`, `kernel_work_afmoe.flash_work`) over the traced seconds
of the ops named `%hg_flash_attention*`."""

import decoder_reads
import kernel_work_afmoe


def read(ctx):
    pairs = decoder_reads.counter(ctx, "causal_pairs")
    if pairs is None or "layer_types" not in ctx["arch"]:
        return None
    flops, nbytes = kernel_work_afmoe.flash_work(
        ctx["arch"], pairs, ctx["window"]["nodes"], kernel_work_afmoe.full_layers(ctx["arch"]))
    return decoder_reads.roofline_share(ctx, "flash_attention", flops, nbytes)
