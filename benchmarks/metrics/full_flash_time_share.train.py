"""Device time of the Mosaic ops named `%hg_flash_attention*` (the causal
flash launches of the FULL layers of a stack that also has sliding ones,
forward and the tiled backward) over device busy time, in the traced span."""

import span_reads


def read(ctx):
    if "layer_types" not in ctx["arch"]:
        return None
    return span_reads.kernel_share_of_busy(ctx, "flash_attention")
