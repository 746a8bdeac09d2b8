"""Model FLOP utilisation of the serving step: the operations the window's
prompt and output tokens needed (each token over exactly the keys before
it; logits only where a token is produced), over window x peak bf16
FLOP/s.  Layer: model step.  Moves serve_tok_s."""


def read(run):
    flops = run.work.get("model_flops", 0.0)
    if flops <= 0 or run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * flops / (run.trace["window_s"] * run.peaks["bf16_flops"])
