"""The one traffic generator: deterministic per seed, inside the mix's
clips, and the same sizes in the same order for every seed."""
import dataclasses
import json

import numpy as np
import pytest

from bench.harness import traffic as tr
from bench_smoke import REPO

MIX = {
    "kind": "serve", "block": 50, "size_seed": 0, "requests": 200,
    "prompt": {"median": 512, "sigma": 0.6, "min": 128, "max": 2048},
    "output": {"median": 128, "sigma": 0.6, "min": 32, "max": 512},
}


def _key(reqs):
    return [(r.prompt.tolist(), r.max_new_tokens) for r in reqs]


def test_same_seed_same_requests_other_seed_other_order():
    a = tr.serve_requests(MIX, 2**33 + 1, vocab=1000)
    b = tr.serve_requests(MIX, 2**33 + 1, vocab=1000)
    c = tr.serve_requests(MIX, 7, vocab=1000)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)
    # the seed draws the tokens; the file fixes every block's order
    assert [(len(r.prompt), r.max_new_tokens) for r in a] == \
        [(len(r.prompt), r.max_new_tokens) for r in c]
    first, second = a[:50], a[50:100]
    assert [len(r.prompt) for r in first] != [len(r.prompt) for r in second]


def test_every_seed_asks_for_the_same_work_per_block():
    want = sorted((s["prompt"], s["output"]) for s in tr.block_sizes(MIX))
    for seed in (1, 2, 3):
        reqs = tr.serve_requests(MIX, seed, vocab=1000)
        for b in range(0, 200, 50):
            blk = reqs[b: b + 50]
            assert sorted((len(r.prompt), r.max_new_tokens)
                          for r in blk) == want


def test_lengths_inside_clips_and_shares_as_stated():
    reqs = tr.serve_requests(MIX, 11, vocab=1000)
    assert len(reqs) == 200 and [r.index for r in reqs] == list(range(200))
    for r in reqs:
        assert MIX["prompt"]["min"] <= len(r.prompt) <= MIX["prompt"]["max"]
        assert MIX["output"]["min"] <= r.max_new_tokens \
            <= MIX["output"]["max"]
        assert r.prompt.dtype == np.int32
        assert r.prompt.min() >= 1 and r.prompt.max() < 1000
    assert np.median([len(r.prompt) for r in reqs]) \
        == pytest.approx(512, rel=0.05)
    assert np.median([r.max_new_tokens for r in reqs]) \
        == pytest.approx(128, rel=0.05)


def test_offline_mix_has_no_due_times_and_fits_max_len():
    mix = json.loads((REPO / "bench/traffic/offline-decode.json").read_text())
    reqs = tr.serve_requests(mix, 5, vocab=32064, n=128)
    # the queue is kept topped up: a request has no time it is due
    assert {f.name for f in dataclasses.fields(tr.RequestSpec)} == {
        "index", "prompt", "max_new_tokens"}
    assert tr.max_context(mix) < mix["max_len"]
    assert max(len(r.prompt) + r.max_new_tokens for r in reqs) \
        <= tr.max_context(mix)
