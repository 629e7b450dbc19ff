"""Device time of the causal flash kernel's launches under latent attention's
head widths (Mosaic ops named `%hg_flash_attention*`: forward, `dq`,
`dk`/`dv`) over device busy time."""

import span_reads


def read(ctx):
    return span_reads.kernel_share_of_busy(ctx, "flash_attention")
