"""What the readers of the program's own regions and kernel names share
(``metrics/<name>.py``). The regions are ``hydragnn_tpu.utils.tracer``'s
totals over the window, every thread's; the kernel names are what
``pl.pallas_call(name="hg_<kernel>")`` puts on a Mosaic op in the device
trace. A region or a name the program does not have reads None."""

from typing import Any, Dict, Optional

KERNEL_PREFIX = "%hg_"


def region_seconds(ctx: Dict[str, Any], name: str) -> Optional[float]:
    return ctx["counters"]["regions"].get(name)


def region_ms_per_step(ctx: Dict[str, Any], name: str) -> Optional[float]:
    total, batches = region_seconds(ctx, name), ctx["window"]["batches"]
    if total is None or not batches:
        return None
    return 1e3 * total / batches


def region_share(ctx: Dict[str, Any], name: str) -> Optional[float]:
    total, seconds = region_seconds(ctx, name), ctx["window"]["seconds"]
    if total is None or seconds <= 0:
        return None
    return 100.0 * total / seconds


def named_mosaic_seconds(ctx: Dict[str, Any], prefix: str) -> Optional[float]:
    """Device seconds of the traced span's Mosaic ops whose instruction name
    starts with ``prefix`` (per chip, as ``busy_s`` is); None when the span
    holds no such op."""
    trace = ctx["trace"]
    if not trace:
        return None
    mine = [sec for name, sec in trace["mosaic_ops"] if name.startswith(prefix)]
    return sum(mine) / max(int(ctx["chips"]), 1) if mine else None


def kernel_share_of_busy(ctx: Dict[str, Any], kernel: str) -> Optional[float]:
    sec = named_mosaic_seconds(ctx, KERNEL_PREFIX + kernel)
    if sec is None or not ctx["trace"]["busy_s"]:
        return None
    return 100.0 * sec / ctx["trace"]["busy_s"]
