"""Statistics, device facts, compile counting and the result line."""
from __future__ import annotations

import json
import math
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_retrieval_time_sec")


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..100) of every value given."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    return xs[max(int(math.ceil(q / 100.0 * len(xs))) - 1, 0)]


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache while
    ``counting`` is set: the measured window should see none."""

    def __init__(self):
        self.counting = False
        self.count = 0
        self._lock = threading.Lock()
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in _COMPILE_EVENTS and self.counting:
            with self._lock:
                self.count += 1


def device_facts(devices: List[Any]) -> Dict[str, Any]:
    """Platform, kind and count as JAX reports them, and the peak memory
    of the fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def emit(correct: bool, attempted: int, failed: int,
         metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
         checks: Dict[str, Dict[str, float]],
         breakdown: Optional[Dict[str, List]] = None) -> None:
    """Print the compared numbers beside their limits as the last lines
    of standard error, then the result as the last line of standard
    output, with the checks under the key that comes last."""
    for name, c in checks.items():
        print(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    out: Dict[str, Any] = {"correct": bool(correct), "attempted": attempted,
                           "failed": failed, "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    print(json.dumps(out), flush=True)


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)
