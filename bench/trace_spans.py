"""Where a traced window's time went, by the program's spans and scopes.

    python3 bench/trace_spans.py --workload <name> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does (set-up, one traced
window, the check, one read of the window's trace) and prints the whole
reduction of ``bench/harness/spans.py``: program span durations, device
idle by the engine thread's innermost span, device own time by innermost
model scope, device time of each step program's executions, and every
per-layer number read from them (``spans.METRICS``), with those that
``BENCHMARK.json`` does not report.  The tables go to standard error as
``[breakdown]`` lines, with the traced window's own end-to-end numbers
(tracing's cost is their distance from an untraced run's); the last line
of standard output is one JSON object: ``correct``, ``checks``, ``e2e``,
``metrics``, ``device`` (``busy_s``, ``window_s``), ``spans`` and
``programs`` (count, total seconds and median milliseconds of each),
``idle_by_span`` and ``scopes``.  Like ``bench/run.py`` it refuses to
run without the cell's chips.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.run import chips_or_refuse, measure  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    return ap.parse_args(argv)


def report(out: dict) -> dict:
    """The result line's object, from a traced ``measure``."""
    from bench.harness import spans

    s = out["trace"]
    return {
        "correct": out["correct"],
        "checks": out["checks"],
        "e2e": out["e2e"],
        "metrics": {k: f(s) for k, f in spans.METRICS.items()},
        "device": {"busy_s": s["busy_s"], "window_s": s["window_s"]},
        **{table: {n: {"n": len(v), "total_s": sum(v),
                       "median_ms": 1e3 * statistics.median(v)}
                   for n, v in sorted(s[table].items())}
           for table in ("spans", "programs")},
        "idle_by_span": s["idle_by_span"],
        "scopes": s["scopes"],
    }


def main(argv=None) -> int:
    args = parse(argv)
    from bench.harness import result, spans
    from bench.harness.manifest import find_cell

    cell = find_cell(args.workload)
    devices = chips_or_refuse(cell.chips)
    if devices is None:
        return 2
    out = measure(cell, args.seed, args.seconds, True, devices)
    spans.log_tables(out["trace"])
    result.log(f"traced window: {json.dumps(out['e2e'])}")
    print(json.dumps(report(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
