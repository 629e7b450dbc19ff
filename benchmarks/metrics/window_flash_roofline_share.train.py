"""Roofline share of the causal flash kernel's SLIDING launches, forward and
backward: the operations and bytes the window's real (query, key) pairs
INSIDE the sliding window require over all sliding layers
(`count:window_pairs`, `kernel_work_afmoe.flash_work`) over the traced seconds
of the ops named `%hg_flash_window*`."""

import decoder_reads
import kernel_work_afmoe


def read(ctx):
    pairs = decoder_reads.counter(ctx, "window_pairs")
    if pairs is None:
        return None
    flops, nbytes = kernel_work_afmoe.flash_work(
        ctx["arch"], pairs, ctx["window"]["nodes"], kernel_work_afmoe.sliding_layers(ctx["arch"]))
    return decoder_reads.roofline_share(ctx, "flash_window", flops, nbytes)
