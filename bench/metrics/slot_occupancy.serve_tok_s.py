"""Share of the engine's slots that decoded, over the window's decode
steps: delta decode_slot_steps / (delta decode_steps x max_slots), from
the ServeEngine's own counters.  Layer: serve engine.  Moves serve_tok_s."""


def read(run):
    steps = run.counters.get("decode_steps", 0)
    if not steps or not run.max_slots:
        return None
    return 100.0 * run.counters["decode_slot_steps"] / (steps * run.max_slots)
