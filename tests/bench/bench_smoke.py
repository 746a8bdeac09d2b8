"""Helpers of the benchmark's CPU tests: a checkout copy in a temp
directory whose cells run at smoke size (the program's reduced configs,
a few short requests or rows), so each cell module runs end to end here."""
import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SERVE_CELL = "phi3-mini.offline-decode"

PHI3_SMOKE = {
    "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 32, "num_hidden_layers": 2,
    "vocab_size": 256, "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
    "program": {"arch": "phi3-mini-3.8b", "smoke": True, "overrides": {}},
    "serve": {"kv_cache_budget_bytes": 8 * 1024 * 64, "slot_multiple": 4},
    "correct": {"logit_gap": 0.1, "min_tokens_checked": 8},
}
OFFLINE_SMOKE = {
    "prompt": {"median": 16, "sigma": 0.5, "min": 8, "max": 40},
    "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
    "block": 8, "requests": 4096, "queue_ahead": 2, "ramp_s": 1,
    "max_len": 64,
}


def _merge(path: pathlib.Path, over: dict) -> None:
    data = json.loads(path.read_text())
    data.update(over)
    path.write_text(json.dumps(data, indent=1))


def make_smoke_root(dst: pathlib.Path) -> pathlib.Path:
    """A copy of the manifest and the benchmark's data files, cut to
    smoke size; the harness code stays the checkout's own."""
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    for sub in ("configs", "traffic", "metrics", "families", "ref"):
        shutil.copytree(REPO / "bench" / sub, dst / "bench" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    _merge(dst / "bench/configs/phi3-mini-3.8b-d8.json", PHI3_SMOKE)
    _merge(dst / "bench/traffic/offline-decode.json", OFFLINE_SMOKE)
    return dst


def load_run_module():
    """``bench/run.py`` as a module (it is a script, not a package part)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_run_script", REPO / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

