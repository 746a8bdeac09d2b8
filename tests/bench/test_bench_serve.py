"""Rehearsal of the serving cell on the CPU at smoke size: the whole run
past the look for a chip, traced and untraced; the control (the reference
in float8) failing the check; and a run with a served token altered where
the engine produces it coming out not correct."""
import types

import numpy as np
import pytest

from bench.harness import manifest, serve_cell as sc
from bench.ref.lowp import fp8_round
from bench_smoke import REPO, SERVE_CELL

SEED = 2**33 + 5


@pytest.fixture
def cell(smoke_root):
    return manifest.find_cell(SERVE_CELL, smoke_root)


def test_untraced_run_reports_end_to_end_metrics(run_script, cpu_peaks,
                                                  cell):
    import jax

    out = run_script.measure(cell, SEED, 2.0, False, jax.devices())
    assert out["correct"] is True
    assert out["window_compiles"] == 0
    assert set(out["metrics"]) == {"serve_tok_s", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["tokens_checked"]["value"] >= 8
    assert out["device"]["platform"] == "cpu"


def test_traced_run_reports_per_layer_metrics(run_script, cpu_peaks, cell):
    import jax

    out = run_script.measure(cell, SEED + 1, 2.0, True, jax.devices())
    assert out["correct"] is True
    got = out["metrics"]
    # the CPU has no device plane: no kernel time, so no roofline share
    assert "decode_attn_roofline.serve_tok_s" not in got
    assert 0 < got["slot_occupancy.serve_tok_s"]["value"] <= 100
    assert 0 < got["mfu.serve_tok_s"]["value"] < 100
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_control_in_float8_fails_the_check(cell):
    run = sc.ServeCell(cell, SEED + 2)
    run.build()
    run.warm()
    win = run.run(1.5, None)
    chosen = sc.sample(sc.finished(win), SEED + 2) + sc.longest_open(win)
    run.free_engine()
    gaps = sc.logit_gaps(cell, run.params, chosen, control=fp8_round)
    limit = cell.config["correct"]["logit_gap"]
    assert gaps["logit_gap"] <= limit < gaps["control_gap"]
    assert gaps["control_gap"] >= 3 * gaps["logit_gap"]


def test_altered_token_makes_the_run_not_correct(run_script, cpu_peaks,
                                                 cell, monkeypatch):
    import jax
    from repro.serve import ServeEngine

    real = ServeEngine.step
    vocab = int(cell.config["vocab_size"])

    def altered(self):
        progressed = real(self)
        for req in self.slots:
            if req is not None and len(req.tokens) == 3:
                req.tokens[-1] = (req.tokens[-1] + 1) % vocab
        return progressed

    monkeypatch.setattr(ServeEngine, "step", altered)
    out = run_script.measure(cell, SEED + 3, 2.0, False, jax.devices())
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


class _Req:
    def __init__(self, p, n, finished_at=None):
        self.prompt_len, self.tokens = p, [0] * n
        self.finished_at = finished_at


def test_sample_holds_the_longest_and_is_seeded():
    done = [sc.Served(None, _Req(p, n))
            for p, n in [(5, 5), (9, 30), (7, 2), (6, 6), (4, 4), (8, 1)]]
    a = sc.sample(done, 11)
    assert a[0].request.prompt_len == 9 and len(a) == sc.SAMPLE_REQUESTS
    assert [s.request.prompt_len for s in sc.sample(done, 11)] == \
        [s.request.prompt_len for s in a]


def test_longest_open_is_the_longest_context_still_decoding():
    served = [sc.Served(None, _Req(p, n, fin)) for p, n, fin in
              [(500, 300, 5.0),     # finished in the window
               (400, 90, None),     # still decoding: 490
               (100, 420, 12.0),    # cut by the stop after the window: 520
               (600, 0, None)]]     # admitted, nothing served yet
    win = {"served": served, "t1": 10.0}
    assert [s.request.prompt_len for s in sc.longest_open(win)] == [100]
    assert sc.longest_open({"served": served[:1], "t1": 10.0}) == []


def test_warm_shapes_start_at_the_shortest_prompts_pages():
    fake = types.SimpleNamespace(
        engine=types.SimpleNamespace(page_size=16, prefill_chunk_tokens=64,
                                     max_pages=96),
        traffic={"prompt": {"min": 64, "max": 512}}, max_len=1536)
    shapes = sc.ServeCell.warm_shapes(fake)
    assert [mb for k, _, mb in shapes if k == "decode"] == \
        [4, 8, 16, 32, 64, 96]
    prefill = {(T, mb) for k, T, mb in shapes if k == "prefill"}
    assert prefill == {(T, mb) for T in (2, 4, 8, 16, 32, 64)
                       for mb in (4, 8, 16, 32)}
    assert len(shapes) == 30


def test_engine_slots_fill_the_cache_budget():
    family = manifest.load_module(REPO / "bench/families/phi3.py",
                                  "bench_family_phi3")
    cfg = {"hidden_size": 8, "num_attention_heads": 2,
           "num_key_value_heads": 2, "head_dim": 4, "intermediate_size": 8,
           "num_hidden_layers": 2, "vocab_size": 8,
           "kv_cache_bytes_per_element": 2,
           "serve": {"kv_cache_budget_bytes": 64 * 100 * 40 + 1,
                     "slot_multiple": 8}}
    # 64 bytes a token, 100 tokens a slot: 40 slots fit, 40 is 5 x 8
    assert sc.engine_slots(cfg, family, 100) == 40
    assert sc._buckets(3, 40, 1, 96) == [4, 8, 16, 32, 64]
    assert sc._buckets(1, 200, 1, 96) == [1, 2, 4, 8, 16, 32, 64, 96]
    assert np.all(np.diff(sc._buckets(1, 64, 2, 1 << 30)) > 0)
