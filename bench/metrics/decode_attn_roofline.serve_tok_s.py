"""Paged decode attention's share of its roofline: the least time the
chip needs for the window's decode attention (every output token over its
prompt and the tokens before it, in every layer: QK and PV operations,
one read of each cached K and V row), over the device time of the
``decode_attention_paged`` Pallas kernel in the trace.  Layer: kernels.
Moves serve_tok_s."""
from bench.harness.peaks import roofline_seconds

OPS = {"decode_attention": r"^decode_attention_paged$"}


def read(run):
    flops, nbytes = run.work.get("decode_attention", (0.0, 0.0))
    spent = (run.trace or {}).get("ops_s", {}).get("decode_attention", 0.0)
    if flops <= 0 or spent <= 0:
        return None
    return 100.0 * roofline_seconds(flops, nbytes, run.peaks) / spent
