"""Median device time of the prefill-chunk program's executions
(``jit_chunk_step`` on the device's XLA Modules line) that end in the
traced window (``spans.prefill_chunk_ms``).  A step that carries a chunk
is the tail of the gaps between tokens.  Layer: model step.  Moves
itl_p95_ms."""
from bench.harness import spans


def read(run):
    return spans.METRICS["prefill_chunk_ms.itl_p95_ms"](run.trace)
