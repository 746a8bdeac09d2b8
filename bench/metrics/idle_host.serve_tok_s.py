"""Share of the traced window (%) in which the device is idle while the
engine's thread is in host work: every ``engine.*`` span but the
fetches, and ``service.take`` (``spans.idle_host``).  Layer: serve
engine.  Moves serve_tok_s."""
from bench.harness import spans


def read(run):
    return spans.METRICS["idle_host.serve_tok_s"](run.trace)
