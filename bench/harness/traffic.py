"""The one generator every serving traffic file feeds.

A serving mix (``"kind": "serve"``) gives lognormal distributions of
prompt and output lengths (``median``, ``sigma``, clipped to ``min`` and
``max``); the serving cell keeps the engine's queue topped up with them.

Sizes come in blocks of ``block`` requests fixed by the file alone
(``size_seed``): each block holds the same lengths, taken at evenly
spaced quantiles of each distribution, in an order drawn per block from
``size_seed``.  The run's ``--seed`` draws only the prompt tokens (and
the weights), so every seed asks for the same work in the same order: a
window finishes a few tens of requests, and a seed that reordered them
would change the work it measures.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Any, Dict, List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class RequestSpec:
    index: int
    prompt: np.ndarray       # int32 token ids
    max_new_tokens: int


def quantile(dist: Dict[str, Any], p: float) -> int:
    """The ``p`` quantile of a lognormal length distribution, clipped and
    rounded."""
    x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(p))
    return int(min(max(round(x), int(dist["min"])), int(dist["max"])))


def block_sizes(traffic: Dict[str, Any]) -> List[Dict[str, int]]:
    """One block's (prompt length, output length) in a fixed order."""
    k = int(traffic["block"])
    rng = np.random.default_rng(int(traffic["size_seed"]))
    ps = [quantile(traffic["prompt"], (i + 0.5) / k) for i in range(k)]
    os_ = [quantile(traffic["output"], (i + 0.5) / k) for i in range(k)]
    pair = rng.permutation(k)  # which output goes with which prompt
    return [{"prompt": ps[i], "output": os_[pair[i]]} for i in range(k)]


def serve_requests(traffic: Dict[str, Any], seed: int, vocab: int,
                   n: Optional[int] = None) -> List[RequestSpec]:
    """The run's request list, in submission order."""
    if traffic["kind"] != "serve":
        raise ValueError(f"not a serving mix: {traffic['kind']!r}")
    n = int(n if n is not None else traffic["requests"])
    block = int(traffic["block"])
    sizes = block_sizes(traffic)
    orders = np.random.default_rng([int(traffic["size_seed"]), 1])
    rng = np.random.default_rng(seed)
    out: List[RequestSpec] = []
    for b in range(-(-n // block)):
        order = orders.permutation(block)
        for j in range(min(block, n - b * block)):
            s = sizes[order[j]]
            out.append(RequestSpec(
                index=b * block + j,
                prompt=rng.integers(1, vocab, s["prompt"], dtype=np.int32),
                max_new_tokens=int(s["output"])))
    return out


def max_context(traffic: Dict[str, Any]) -> int:
    """Longest prompt plus output the mix can send."""
    return int(traffic["prompt"]["max"]) + int(traffic["output"]["max"])
