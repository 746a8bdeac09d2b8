"""Readings the benchmark's limits and sizes are set from, on the chip.

Not part of a benchmark run: ``bench/run.py`` never calls this.  It runs
in one process, so the chip is never shared.

    python3 bench/calibrate.py serve --workload <cell> --seeds 1,2,3 \
        --seconds 10 [--control 1,2,3]
        The served tokens' widest logit gap on each seed, and on the
        ``--control`` seeds the same for the reference in float8, over
        the requests a run of the cell checks: one JSON line a seed on
        standard error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.harness import checkout  # noqa: E402

checkout.setup_process()


def _ints(s: str):
    return [int(x) for x in s.split(",") if x]


def serve(args) -> None:
    """Program and control readings of the serving check, seed by seed,
    with one engine whose weights are made anew for each seed."""
    import jax
    from bench.harness import result, serve_cell as sc
    from bench.harness.manifest import find_cell
    from bench.harness.weights import make_weights
    from bench.ref.lowp import fp8_round
    from repro.train.state import model_specs

    cold = checkout.enable_cache()
    cell = find_cell(args.workload)
    seeds, control = _ints(args.seeds), set(_ints(args.control or ""))
    c = sc.ServeCell(cell, seeds[0])
    c.build()
    c.warm(precompile=cold)
    engine = c.engine
    for seed in seeds:
        c.seed = seed
        engine.params = None
        c.params = make_weights(model_specs(c.cfg), seed)
        engine.params = jax.device_put(c.params, jax.devices()[0])
        engine._init_state()
        c.engine = engine
        win = c.run(args.seconds, None)
        done = sc.finished(win)
        chosen = sc.sample(done, seed) + sc.longest_open(win)
        keep_cache, engine.cache = engine.cache, None
        del keep_cache
        gaps = sc.logit_gaps(cell, c.params, chosen,
                             control=fp8_round if seed in control else None)
        row = {"seed": seed, **gaps, "finished": len(done),
               "e2e": sc.e2e_metrics(win)}
        result.log(json.dumps(row))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("serve",))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--control", default="")
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate needs a TPU", file=sys.stderr)
        return 2
    serve(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
