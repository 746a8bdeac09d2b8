"""Median duration of the ``engine.decode`` program spans that end in the
traced window: building the step's inputs, the decode step, the fetch of
its tokens and their bookkeeping (``spans.decode_step_ms``).  Layer:
model step.  Moves serve_tok_s."""
from bench.harness import spans


def read(run):
    return spans.METRICS["decode_step_ms.serve_tok_s"](run.trace)
