"""What the readers of the decoder cell's counters and kernel rooflines share.
The counters are sums over the window (``hydragnn_tpu.utils.tracer.count`` at
each epoch drain); a program without them reads None."""

from typing import Any, Dict, Optional

import kernel_work
import span_reads


def counter(ctx: Dict[str, Any], name: str) -> Optional[float]:
    return ctx["counters"]["regions"].get("count:" + name)


def roofline_share(ctx: Dict[str, Any], kernel: str, flops: float, nbytes: float) -> Optional[float]:
    """The kernel's required work a second of the window (a steady rate) over
    its traced seconds a second of the span, against the chip's peaks:
    100 x least seconds / seconds taken. The span's steps re-run each layer's
    forward (rematerialisation), which the required work leaves out."""
    sec = span_reads.named_mosaic_seconds(ctx, span_reads.KERNEL_PREFIX + kernel)
    trace, seconds = ctx["trace"], ctx["window"]["seconds"]
    if sec is None or not ctx["peaks"] or not trace or not trace["window_s"] or seconds <= 0:
        return None
    least_per_s = kernel_work.roofline_seconds(flops, nbytes, ctx["peaks"]) / seconds
    return 100.0 * least_per_s / (sec / trace["window_s"])
