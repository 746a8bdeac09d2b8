"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics are
found by name from ``BENCHMARK.json``.  Set-up (weights from the seed,
building the program, compiling every shape the window uses, filling the
engine) runs first; then the window measures for ``--seconds``.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window (one read: operations, program spans and executions, model
scopes) and the program's counters.  Afterwards the plain reference checks
what the window produced, and the last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``breakdown`` when traced) and ``checks``, each compared number beside its
limit.  The run refuses, with a non-zero exit and no result, where JAX finds
no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.harness import checkout  # noqa: E402

checkout.setup_process()


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips_or_refuse(need: int, platform: str = "tpu"):
    """The devices to run on, or ``None`` after saying why not."""
    import jax

    devices = jax.devices()
    found = devices[0].platform
    if found != platform:
        print(f"bench needs a {platform.upper()}; JAX found platform "
              f"{found!r} ({len(devices)} device(s))", file=sys.stderr)
        return None
    if len(devices) < need:
        print(f"the cell needs {need} chips; JAX found {len(devices)}",
              file=sys.stderr)
        return None
    return devices[:need]


def per_layer(readers, cell, run) -> dict:
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for m in cell.per_layer:
        value = readers[m["name"]].read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def measure(cell, seed: int, seconds: float, trace: bool, devices) -> dict:
    """Set-up, window and check of one cell by the cell module its traffic's
    ``kind`` names (``bench/harness/<kind>_cell.py``); returns the
    result's parts."""
    import importlib

    from bench.harness import result, spans, trace as tr
    from bench.harness.manifest import metric_module
    from bench.harness.peaks import peaks_for

    cold = checkout.enable_cache()
    peaks = peaks_for(devices[0].device_kind)
    compiles = result.CompileCounter()
    trace_dir = None
    if trace:
        trace_dir = str(checkout.TRACE_DIR / cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    cell_module = importlib.import_module(
        f"bench.harness.{cell.traffic['kind']}_cell")
    out = cell_module.measure(cell, seed, seconds, trace_dir, compiles,
                              devices, PROCESS_START, precompile=bool(cold))
    result.log(f"compilations inside the window: {compiles.count}")
    out["window_compiles"] = compiles.count
    metrics = {}
    if trace:
        readers = {m["name"]: metric_module(m["name"], cell.root / "bench")
                   for m in cell.per_layer}
        ops = {k: re.compile(pattern).search for r in readers.values()
               for k, pattern in getattr(r, "OPS", {}).items()}
        # the trace names each operation's HLO instruction, not its
        # op_name: the compiled programs map one to the other
        profile = spans.read_spans(tr.find_profile(trace_dir),
                                   spans.op_names_from_hlo(out.pop("hlo")))
        summary = spans.window_summary(profile, ops,
                                       spans.cell_scopes(cell.family()))
        shutil.rmtree(trace_dir, ignore_errors=True)
        out["trace"] = summary
        run = types.SimpleNamespace(
            config=cell.config, peaks=peaks, trace=summary,
            counters=out["counters"], work=out["work"],
            max_slots=out.get("max_slots"), e2e=out["e2e"])
        metrics = per_layer(readers, cell, run)
        out["device"]["busy_s"] = summary["busy_s"]
        out["device"]["window_s"] = summary["window_s"]
        out["breakdown"] = summary["breakdown"]
    else:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": out["setup_s"],
                                      "unit": m["unit"]}
            elif m["name"] in out["e2e"]:
                metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                      "unit": m["unit"]}
    out["metrics"] = metrics
    return out


def main(argv=None) -> int:
    args = parse(argv)
    missing = checkout.missing_program()
    if missing:
        print(f"the checkout lacks the program under test: {missing}",
              file=sys.stderr)
        return 2
    from bench.harness.manifest import find_cell

    cell = find_cell(args.workload)
    devices = chips_or_refuse(cell.chips)
    if devices is None:
        return 2
    out = measure(cell, args.seed, args.seconds, bool(args.trace), devices)
    from bench.harness import result

    result.emit(out["correct"], out["attempted"], out["failed"],
                out["metrics"], out["device"], out["checks"],
                out.get("breakdown"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
