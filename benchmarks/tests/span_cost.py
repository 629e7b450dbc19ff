"""By hand, on the chip's host: what one ``tr.start`` / ``tr.stop`` pair of
``hydragnn_tpu.utils.tracer`` costs with no profiler running.

    python3 benchmarks/tests/span_cost.py [<root of another checkout>]

Prints one JSON line: microseconds a pair, bare and (where ``start`` takes
them) with ``batch=`` and ``epoch=``, best of five rounds of 100k pairs."""

import json
import os
import sys
import time

ROOT = os.path.abspath(sys.argv[1]) if len(sys.argv) > 1 else os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from hydragnn_tpu.utils import tracer as tr  # noqa: E402


def pair_us(n: int = 100_000, **attrs) -> float:
    best = float("inf")
    for _ in range(5):
        t = time.perf_counter()
        for i in range(n):
            tr.start("span_cost", **attrs)
            tr.stop("span_cost")
        best = min(best, time.perf_counter() - t)
    return 1e6 * best / n


def main():
    tr.reset()
    tr.enable()
    out = {"root": ROOT, "bare_us": pair_us()}
    try:
        out["with_attrs_us"] = pair_us(batch=7, epoch=3)
    except TypeError:  # a recorder whose start() takes no attributes
        out["with_attrs_us"] = None
    tr.disable()
    out["disabled_us"] = pair_us()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
