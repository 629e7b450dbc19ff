"""Mosaic (Pallas) time under an `hg_` name over all Mosaic time of the
traced span: 100 while every kernel keeps its name."""

import span_reads


def read(ctx):
    named = span_reads.named_mosaic_seconds(ctx, span_reads.KERNEL_PREFIX)
    if named is None or not ctx["trace"]["mosaic_s"]:
        return None
    return 100.0 * named / ctx["trace"]["mosaic_s"]
