"""Host milliseconds a step inside the staging thread's `h2d_stage` region,
the `jax.device_put` call of `device_prefetch` (the call, not the copy on
the wire): the window's total over its batches."""

import span_reads


def read(ctx):
    return span_reads.region_ms_per_step(ctx, "h2d_stage")
