"""The Phi-3 decoder family (``model_type`` "phi3"): how a configuration
file maps onto the program, and the operations and bytes its tokens need.

A configuration file names its family in ``model_type``; the harness
loads ``bench/families/<model_type>.py`` for these functions and
``bench/ref/<model_type>.py`` for the plain reference, so a new family is
two new files.

The counts are the yardstick of every roofline and MFU the benchmark
reports.  They count what the mathematics of the model needs, not what an
implementation happens to do: no padding, no recomputation, no second
read of a weight, and the attention of a token over exactly the keys
before it.  A multiply-add is two operations.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple


def program_config(config: Dict[str, Any]):
    """The program's ModelConfig for a configuration file, with a check
    that every width the file states is the one the program runs."""
    from repro.configs import get_config

    prog = config["program"]
    cfg = get_config(prog["arch"], smoke=bool(prog.get("smoke", False)))
    cfg = cfg.with_overrides(**prog.get("overrides", {}))
    want = {"d_model": config["hidden_size"],
            "num_heads": config["num_attention_heads"],
            "num_kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "d_ff": config["intermediate_size"],
            "num_layers": config["num_hidden_layers"],
            "vocab_size": config["vocab_size"],
            "rope_theta": config["rope_theta"],
            "norm_eps": config["rms_norm_eps"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"program config {got} differs from the "
                         f"configuration file {want}")
    return cfg


def _dims(c: Dict) -> Tuple[int, int, int, int, int, int, int]:
    d = int(c["hidden_size"])
    h = int(c["num_attention_heads"])
    kv = int(c["num_key_value_heads"])
    dh = int(c.get("head_dim", d // h))
    return (d, h, kv, dh, int(c["intermediate_size"]),
            int(c["num_hidden_layers"]), int(c["vocab_size"]))


def layer_matmul_params(c: Dict) -> int:
    d, h, kv, dh, f, _, _ = _dims(c)
    return d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f


def token_flops(c: Dict, keys: int, logits: bool) -> float:
    """One token through every layer, attending ``keys`` positions
    (itself included), plus the output head when its logits are needed."""
    d, h, _, dh, _, layers, vocab = _dims(c)
    flops = 2.0 * layers * layer_matmul_params(c)
    flops += 4.0 * layers * h * dh * keys
    if logits:
        flops += 2.0 * d * vocab
    return flops


def prompt_flops(c: Dict, prompt: int) -> float:
    """A whole prompt: every position attends the ones before it; logits
    only for the last, which gives the first output token."""
    d, h, _, dh, _, layers, vocab = _dims(c)
    flops = 2.0 * layers * layer_matmul_params(c) * prompt
    flops += 4.0 * layers * h * dh * prompt * (prompt + 1) / 2
    return flops + 2.0 * d * vocab


def decode_attention_work(c: Dict, keys: int) -> Tuple[float, float]:
    """(operations, bytes) of one token's decode attention over ``keys``
    cached positions in every layer: QK and PV, and one read of each K
    and V row in the cache's dtype."""
    _, h, kv, dh, _, layers, _ = _dims(c)
    kv_bytes = int(c["kv_cache_bytes_per_element"])
    flops = 4.0 * layers * h * dh * keys
    nbytes = 2.0 * layers * kv * dh * kv_bytes * keys
    return flops, nbytes


def kv_bytes_per_token(c: Dict) -> int:
    _, _, kv, dh, _, layers, _ = _dims(c)
    return 2 * layers * kv * dh * int(c["kv_cache_bytes_per_element"])
