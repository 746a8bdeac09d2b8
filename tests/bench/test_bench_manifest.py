"""The manifest and how the harness finds a cell's parts by name; the
result line; and ``bench/run.py`` refusing to run without a TPU or without
the program under test."""
import io
import json
import os
import re
import shutil
import subprocess
import sys
from contextlib import redirect_stdout, redirect_stderr

import pytest

from bench.harness import manifest, result
from bench_smoke import REPO, make_smoke_root

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_manifest_keys_names_and_files():
    man = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in man["configs"]]
             + [w["name"] for w in man["workloads"]]
             + [m["name"] for m in man["end_to_end"] + man["per_layer"]])
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    for c in man["configs"]:
        assert (REPO / c["file"]).is_file()
        assert any(c["file"].startswith(p + "/") for p in man["paths"])
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for w in man["workloads"]:
        assert (REPO / "bench/traffic" / f"{w['traffic']}.json").is_file()
        reported = [m for m in man["end_to_end"]
                    if "workloads" not in m or w["name"] in m["workloads"]]
        assert len(reported) >= 2
    for m in man["per_layer"]:
        assert (REPO / "bench/metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in e2e[m["moves"]].get("workloads", [w])


def test_every_cell_is_found_with_its_metrics():
    man = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in man["workloads"]:
        cell = manifest.find_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.per_layer and any(m["name"] == "setup_s"
                                      for m in cell.end_to_end)
        for m in cell.per_layer:
            assert callable(manifest.metric_module(m["name"]).read)


def test_a_new_cell_mix_and_metric_are_found_by_name(tmp_path):
    """A later PR adds files and manifest entries only."""
    root = make_smoke_root(tmp_path)
    man = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/phi3-mini-3.8b-d8.json")
                     .read_text())
    cfg["name"] = "phi3-mini-3.8b-d4"
    (root / "bench/configs/phi3-mini-3.8b-d4.json").write_text(
        json.dumps(cfg))
    mix = json.loads((root / "bench/traffic/offline-decode.json")
                     .read_text())
    mix["output"]["median"] = 4
    (root / "bench/traffic/short-out.json").write_text(json.dumps(mix))
    (root / "bench/metrics/queue_depth.serve_tok_s.py").write_text(
        "def read(run):\n    return 42.0\n")
    man["configs"].append({"name": "phi3-mini-3.8b-d4", "source": "x",
                           "file": "bench/configs/phi3-mini-3.8b-d4.json",
                           "reduced": ["num_hidden_layers"], "why": "x"})
    man["workloads"].append({"name": "phi3-mini.short-out",
                             "config": "phi3-mini-3.8b-d4",
                             "traffic": "short-out", "chips": 1, "why": "x"})
    man["per_layer"].append({"name": "queue_depth.serve_tok_s", "unit": "%",
                             "better": "lower", "source": "program_counter",
                             "layer": "serve engine", "moves": "serve_tok_s",
                             "workloads": ["phi3-mini.short-out"]})
    for m in man["end_to_end"]:
        if m["name"] in ("serve_tok_s", "itl_p95_ms"):
            m["workloads"].append("phi3-mini.short-out")
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    cell = manifest.find_cell("phi3-mini.short-out", root)
    assert cell.config["name"] == "phi3-mini-3.8b-d4"
    assert cell.traffic["output"]["median"] == 4
    assert [m["name"] for m in cell.per_layer] == ["queue_depth.serve_tok_s"]
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tok_s", "itl_p95_ms", "setup_s"}
    read = manifest.metric_module("queue_depth.serve_tok_s", root / "bench").read
    assert read(None) == 42.0


TOY_FAMILY = """
import pathlib
from bench.harness.manifest import load_module
_base = load_module(pathlib.Path(__file__).with_name("phi3.py"), "toy_base")
CALLS = []

def program_config(config):
    CALLS.append("program_config")
    return _base.program_config(config)

def kv_bytes_per_token(c):
    CALLS.append("kv_bytes_per_token")
    return _base.kv_bytes_per_token(c)

token_flops = _base.token_flops
prompt_flops = _base.prompt_flops
SCOPES = ("toy.scope",)


def token_work(c, keys):
    return {"decode_attention": _base.decode_attention_work(c, keys),
            "toy.latent": (1.0, 0.0)}


def prompt_work(c, prompt):
    return {"toy.latent": (0.0, 1.0)}
"""
# the toy's per-layer metrics: device time in its scope, its counter over
# the decode steps, and its named work (output tokens, prompts)
TOY_METRICS = {
    "toy_scope_ns.serve_tok_s":
        "return 1e9 * run.trace['scopes'].get('toy.scope', 0.0) or None",
    "toy_routed.serve_tok_s":
        "return run.counters['toy_routed'] / run.counters['decode_steps']",
    "toy_latent.serve_tok_s":
        "return run.work['toy.latent'][0] + 1e-6 * run.work['toy.latent'][1]",
}
TOY_REF = """
import pathlib
from bench.harness.manifest import load_module
_base = load_module(pathlib.Path(__file__).with_name("phi3.py"), "toy_ref")
CALLS = []

def forward(params, tokens, config, quant=None):
    CALLS.append(int(tokens.shape[0]))
    return _base.forward(params, tokens, config, quant)
"""


def _harness_files():
    return {p: p.read_bytes() for p in sorted((REPO / "bench").rglob("*.py"))
            if "__pycache__" not in p.parts}


def test_a_new_model_family_is_found_by_model_type(tmp_path, run_script,
                                                   cpu_peaks, monkeypatch):
    """A configuration of a new ``model_type`` brings its family and its
    reference as two new files, and per-layer metrics as files of their
    own; the serving cell runs it end to end through them, traced, with
    no harness file changed: the family's scope, its work item and an
    engine counter it adds each reach the metrics' ``read(run)``."""
    import jax
    from repro.serve import ServeEngine

    from bench.harness import spans

    before = _harness_files()
    stats = ServeEngine.stats

    def with_counter(self):      # a counter the new family's model adds
        out = stats(self)
        out["toy_routed"] = 3 * out.get("decode_steps", 0)
        return out

    read_spans = spans.read_spans

    def with_device(path, op_names=None, **where):
        # the CPU has no device plane: lend the trace one operation in the
        # family's scope, 20 ns into the window
        prof = read_spans(path, op_names, **where)
        lo, _ = prof.window()
        prof.devices["/device:TPU:0"] = [spans.DeviceOp(
            "%fusion.1 = f32[2]{0} fusion(", lo + 10, lo + 30,
            op_name="jit(_step)/decode_step/layers/toy.scope/dot")]
        return prof

    monkeypatch.setattr(ServeEngine, "stats", with_counter)
    monkeypatch.setattr(spans, "read_spans", with_device)
    root = make_smoke_root(tmp_path)
    cfg = json.loads((root / "bench/configs/phi3-mini-3.8b-d8.json")
                     .read_text())
    cfg.update(name="toy-d2", model_type="toy")
    (root / "bench/configs/toy-d2.json").write_text(json.dumps(cfg))
    (root / "bench/families/toy.py").write_text(TOY_FAMILY)
    (root / "bench/ref/toy.py").write_text(TOY_REF)
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "toy-d2", "source": "x",
                           "file": "bench/configs/toy-d2.json",
                           "reduced": [], "why": "x"})
    man["workloads"].append({"name": "toy.offline-decode",
                             "config": "toy-d2", "traffic": "offline-decode",
                             "chips": 1, "why": "x"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("toy.offline-decode")
    for name, body in TOY_METRICS.items():
        (root / "bench/metrics" / f"{name}.py").write_text(
            f"def read(run):\n    {body}\n")
        man["per_layer"].append({
            "name": name, "unit": "1", "better": "higher",
            "source": "program_counter", "layer": "toy",
            "moves": "serve_tok_s", "workloads": ["toy.offline-decode"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    cell = manifest.find_cell("toy.offline-decode", root)
    out = run_script.measure(cell, 2**32 + 77, 1.0, True, jax.devices())
    assert out["correct"] is True
    assert {"program_config", "kv_bytes_per_token"} <= set(
        cell.family().CALLS)
    assert cell.reference().CALLS and set(cell.reference().CALLS) == {
        int(cell.traffic["max_len"])}
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert got["toy_scope_ns.serve_tok_s"] == pytest.approx(20.0)
    assert got["toy_routed.serve_tok_s"] == 3.0
    tokens, prompts = out["work"]["toy.latent"]
    assert tokens >= 1 and prompts >= 1
    assert got["toy_latent.serve_tok_s"] == tokens + 1e-6 * prompts
    # the toy's work item rides beside the decode attention it still gives
    assert "decode_attn_roofline.serve_tok_s" not in got   # no kernel here
    assert out["work"]["decode_attention"][0] > 0
    assert _harness_files() == before


def test_last_line_holds_the_contract_keys_and_checks_last():
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        result.emit(True, 10, 1, {"serve_tok_s": {"value": 1.5,
                                                  "unit": "tokens/s"}},
                    {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                     "memory_peak_bytes": 5},
                    {"logit_gap": {"value": 0.1, "limit": 0.5}},
                    {"device_ops": [["fusion", 0.5]], "idle_gaps": []})
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "checks"]
    assert line["checks"]["logit_gap"] == {"value": 0.1, "limit": 0.5}
    assert err.getvalue().strip().splitlines()[-1].startswith(
        "[check] logit_gap = 0.1 (limit 0.5)")


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert result.percentile(xs, 95) == 95
    assert result.percentile([3.0], 95) == 3.0
    assert result.percentile([1, 2, 3, 4], 50) == 2


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "phi3-mini.offline-decode", "--seed", "3", "--seconds", "1",
         "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_backend_that_is_not_a_tpu():
    r = _run(REPO)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert "{" not in r.stdout


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert "lacks the program" in r.stderr
    assert "{" not in r.stdout
