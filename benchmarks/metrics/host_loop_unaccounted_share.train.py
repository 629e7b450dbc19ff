"""Share of the window the main thread spent under NO region: the window
less `dataload`, `rng_split`, `train_step` and `epoch_drain` (`epoch_restart`
encloses a `dataload` and `dispatch` is a child of `train_step`, so neither
is in the sum)."""

import span_reads


def read(ctx):
    spans = [span_reads.region_seconds(ctx, n) for n in ("dataload", "train_step", "epoch_drain")]
    seconds = ctx["window"]["seconds"]
    if None in spans or seconds <= 0:
        return None
    covered = sum(spans) + (span_reads.region_seconds(ctx, "rng_split") or 0.0)
    return 100.0 * (seconds - covered) / seconds
