"""Where a traced window's time went, by the program's spans and scopes.

    python3 bench/trace_spans.py --workload <name> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does (set-up, one traced
window, the check) and reduces the window's trace with
``bench/harness/spans.py``: program span durations, device idle by the
engine thread's innermost span, device own time by innermost model scope
(operations mapped to their scopes through the compiled HLO of every
step program the cell warms, taken after the window), device time of
each step program's executions, and the per-layer numbers read from
them.  The tables go to standard error as ``[breakdown]`` lines, with
the traced window's own end-to-end numbers (tracing's cost is their
distance from an untraced run's); the last line of standard output is
one JSON object: ``correct``, ``checks``, ``e2e``, ``metrics``,
``device`` (``busy_s``, ``window_s``), ``spans`` and ``programs``
(count, total seconds and median milliseconds of each),
``idle_by_span`` and ``scopes``.  Like ``bench/run.py`` it refuses to
run without the cell's chips.
"""
from __future__ import annotations

import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.harness import checkout  # noqa: E402

checkout.setup_process()


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    return ap.parse_args(argv)


def traced_window(cell, seed: int, seconds: float, devices) -> dict:
    """Set-up, one traced window and the check of a cell; the cell
    module's result with the reduced trace under ``trace``."""
    from bench.harness import result, spans, trace as tr

    cold = checkout.enable_cache()
    trace_dir = str(checkout.TRACE_DIR / f"{cell.name}.spans")
    shutil.rmtree(trace_dir, ignore_errors=True)
    cell_module = importlib.import_module(
        f"bench.harness.{cell.traffic['kind']}_cell")
    out = cell_module.measure(cell, seed, seconds, trace_dir,
                              result.CompileCounter(), devices,
                              PROCESS_START, precompile=bool(cold))
    # the trace names each operation's HLO instruction, not its op_name:
    # the compiled programs map one to the other
    op_names = spans.op_names_from_hlo(hlo_texts(cell, seed))
    out["trace"] = spans.summarize(
        spans.read_spans(tr.find_profile(trace_dir), op_names))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return out


def hlo_texts(cell, seed: int):
    """The compiled HLO text of every step program a serving cell warms
    (loaded from the persistent cache the window filled), from an engine
    built again at the cell's sizes."""
    from bench.harness.serve_cell import ServeCell

    run = ServeCell(cell, seed)
    run.build()
    try:
        texts = []
        for shape in run.warm_shapes():
            fn, args, kw = run._args(*shape)
            texts.append(fn.lower(*args, **kw).compile().as_text())
        return texts
    finally:
        run.free_engine()


def report(out: dict) -> dict:
    """The result line's object."""
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
    from bench.run import chips_or_refuse

    cell = find_cell(args.workload)
    devices = chips_or_refuse(cell.chips)
    if devices is None:
        return 2
    out = traced_window(cell, args.seed, args.seconds, devices)
    spans.log_tables(out["trace"])
    result.log(f"traced window: {json.dumps(out['e2e'])}")
    print(json.dumps(report(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
