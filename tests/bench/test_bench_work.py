"""The yardstick's operation and byte counts, against counts made by hand
on small shapes: the Phi-3 family's (``bench/families/phi3.py``) and the
roofline."""
import json

import pytest

from bench.harness.manifest import load_module
from bench.harness.peaks import roofline_seconds
from bench_smoke import REPO

work = load_module(REPO / "bench/families/phi3.py", "bench_family_phi3")

DEC = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
       "head_dim": 4, "intermediate_size": 16, "num_hidden_layers": 3,
       "vocab_size": 10, "kv_cache_bytes_per_element": 2}


def test_decoder_layer_params_by_hand():
    # q 8x2x4, k and v 8x1x4 each, o 2x4x8, gate/up/down 3 x 8x16
    assert work.layer_matmul_params(DEC) == 64 + 64 + 64 + 384


def test_decoder_token_flops_by_hand():
    # 2 x 3 layers x 576 params; attention 4 x 3 x 2 heads x 4 x 5 keys;
    # head 2 x 8 x 10
    assert work.token_flops(DEC, keys=5, logits=False) == 3456 + 480
    assert work.token_flops(DEC, keys=5, logits=True) \
        == 3456 + 480 + 160


def test_decoder_prompt_is_its_tokens_with_one_head():
    per = sum(work.token_flops(DEC, keys=k, logits=False)
              for k in range(1, 8))
    assert work.prompt_flops(DEC, 7) == pytest.approx(per + 160)


def test_attention_work_by_hand():
    f, b = work.decode_attention_work(DEC, keys=6)
    assert f == 4 * 3 * 2 * 4 * 6
    assert b == 2 * 3 * 1 * 4 * 2 * 6       # K and V rows, bf16
    assert work.kv_bytes_per_token(DEC) == 2 * 3 * 4 * 2


def test_roofline_takes_the_slower_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline_seconds(1000, 20, peaks) == 10.0
    assert roofline_seconds(100, 50, peaks) == 5.0


def test_configs_carry_what_the_counts_read():
    phi3 = json.loads((REPO / "bench/configs/phi3-mini-3.8b-d8.json")
                      .read_text())
    # 113.25 M parameters a layer, as the published widths give
    assert work.layer_matmul_params(phi3) == 113_246_208
    assert work.kv_bytes_per_token(phi3) == 98_304
