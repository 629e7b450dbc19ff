"""Host milliseconds a step that the loader spends building ONE batch
(collate, pack or ladder choice, pad) in its `batch_build` region, on the
producer thread: the window's total over its batches."""

import span_reads


def read(ctx):
    return span_reads.region_ms_per_step(ctx, "batch_build")
