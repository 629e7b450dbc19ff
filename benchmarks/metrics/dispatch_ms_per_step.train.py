"""Host milliseconds a step inside the main loop's `dispatch` region,
`step_fn(state, batch, sub)` alone: the window's total over its batches."""

import span_reads


def read(ctx):
    return span_reads.region_ms_per_step(ctx, "dispatch")
