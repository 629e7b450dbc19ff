"""Roofline share of the causal flash kernel under latent attention's head
widths, forward and backward: the operations and bytes the window's real
(query, key) pairs require at 192-wide scores and 128-wide values
(`kernel_work_joyai.mla_flash_work`) over the traced seconds of the ops named
`%hg_flash_attention*`."""

import decoder_reads
import kernel_work_joyai


def read(ctx):
    pairs = decoder_reads.counter(ctx, "causal_pairs")
    if pairs is None or "qk_nope_head_dim" not in ctx["arch"]:
        return None
    flops, nbytes = kernel_work_joyai.mla_flash_work(ctx["arch"], pairs, ctx["window"]["nodes"])
    return decoder_reads.roofline_share(ctx, "flash_attention", flops, nbytes)
