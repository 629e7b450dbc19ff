"""By hand: turn a trace dump of `tests/trace_tools.py` (made on the chip) into
the small recorded trace under fixtures/ that the reduction is tested against.

    python benchmarks/tests/make_fixture.py chiprun_out/<cell>_trace.json <cell> <step_prefix> <seconds>

The `expect` numbers are what the reduction reads on those rows today; check
them by hand against the rows before committing the fixture."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import tracing  # noqa: E402


def main():
    dump, cell, prefix = sys.argv[1:4]  # argv[4]: seconds of the dump to keep
    rows = [tuple(r) for r in json.load(open(dump))["rows_sample"]]
    t0 = min(r[3] for r in rows)
    # two steps are enough; HLO text cut to `%name type[shape] opcode`
    rows = [(r[0], r[1], tracing.short(r[2]) if r[0].startswith(tracing.DEVICE_PREFIX) else r[2],
             r[3] - t0, r[4]) for r in rows if r[3] - t0 <= float(sys.argv[4]) * 1e9]
    got = tracing.reduce_events(rows, prefix, 1)
    keys = ("window_s", "busy_s", "mosaic_s", "ops_s", "step_ms_p50", "steps")
    out = {"step_prefix": prefix, "rows": rows, "expect": {k: got[k] for k in keys},
           "expect_gap_labels": [g[0] for g in got["idle_gaps"]]}
    path = os.path.join(os.path.dirname(HERE), "fixtures", f"{cell}.trace_rows.json")
    with open(path, "w") as f:
        json.dump(out, f)
    print(path, os.path.getsize(path), "bytes", len(rows), "rows", out["expect"], out["expect_gap_labels"])


if __name__ == "__main__":
    main()
