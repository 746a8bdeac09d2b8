"""Find a cell's parts by the names ``BENCHMARK.json`` gives them.

* a configuration: the ``file`` its entry names (``bench/configs/``);
* a model family: the configuration's ``model_type`` names
  ``bench/families/<model_type>.py`` (the program's config for the file,
  and the family's operation and byte counts) and
  ``bench/ref/<model_type>.py`` (the plain reference);
* a traffic mix: ``bench/traffic/<traffic>.json``, whose ``kind`` names
  the cell module ``bench/harness/<kind>_cell.py``;
* a per-layer metric: ``bench/metrics/<name>.py``, a module with
  ``read(run) -> float | None`` (see ``metric_module``).

A family file may also declare what its model adds, for its metrics:

* ``SCOPES``: a tuple of ``jax.named_scope`` names; a traced run counts
  device time under them beside the harness's own (``spans.SCOPES``);
* ``token_work(config, keys) -> {name: (operations, bytes)}`` for each
  output token that attends ``keys`` positions, and ``prompt_work(config,
  prompt) -> {name: (operations, bytes)}`` for each prompt; the window's
  sums land in ``run.work[name]``.  Without ``token_work`` the family's
  ``decode_attention_work`` gives ``run.work["decode_attention"]``.

What a reader's ``run`` holds: ``config``, ``peaks``, ``max_slots``,
``e2e``; ``counters``, the window's change in every numeric counter the
engine's ``stats()`` gives; ``work``, ``model_flops`` and the family's
named ``(operations, bytes)``; and in a traced run ``trace``: ``window_s``,
``busy_s``, ``ops_s`` (seconds of the operation kinds a reader's ``OPS``
selects), ``breakdown``, ``spans`` (durations of each program span),
``idle_by_span``, ``scopes`` (device own time by model scope) and
``programs`` (device time of each step program's executions).

Adding a cell, a mix, a family or a metric adds files and manifest
entries; no file of the harness changes.  A new cell appends its name to
the ``workloads`` list of every metric it reports, end-to-end and
per-layer.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Dict, List

from bench.harness.checkout import BENCH, CHECKOUT


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]        # the configuration file's contents
    traffic: Dict[str, Any]       # the traffic file's contents
    end_to_end: List[Dict[str, Any]]   # metric entries this cell reports
    per_layer: List[Dict[str, Any]]
    root: pathlib.Path            # checkout root the files came from

    def family(self):
        """``bench/families/<model_type>.py`` of this cell's configuration."""
        mt = self.config["model_type"]
        return load_module(self.root / "bench" / "families" / f"{mt}.py",
                           "bench_family_" + mt)

    def reference(self):
        """``bench/ref/<model_type>.py``: its ``forward(params, tokens,
        config, quant=None)``."""
        mt = self.config["model_type"]
        return load_module(self.root / "bench" / "ref" / f"{mt}.py",
                           "bench_ref_" + mt)


def load_manifest(root: pathlib.Path = CHECKOUT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: pathlib.Path = CHECKOUT) -> Cell:
    man = load_manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in man["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in man["end_to_end"] if _reports(m, name)]
    per_layer = [m for m in man["per_layer"] if _reports(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                root=root)


_LOADED: Dict[pathlib.Path, Any] = {}


def load_module(path: pathlib.Path, name: str):
    """A module from its file, loaded once per process (a reference's
    jitted functions then compile once)."""
    path = pathlib.Path(path).resolve()
    if path not in _LOADED:
        spec = importlib.util.spec_from_file_location(
            name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def metric_module(name: str, bench: pathlib.Path = BENCH):
    """``bench/metrics/<name>.py``: its ``read(run) -> float | None`` and,
    where it reads a kernel's time, ``OPS`` (key -> regex over the trace's
    operation kinds)."""
    return load_module(bench / "metrics" / f"{name}.py",
                       "bench_metric_" + name)
