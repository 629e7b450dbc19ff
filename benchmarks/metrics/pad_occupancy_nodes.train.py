"""Real over padded node slots of the window's batches (a count)."""


def read(ctx):
    w = ctx["window"]
    return 100.0 * w["nodes"] / w["node_slots"] if w["node_slots"] else None
