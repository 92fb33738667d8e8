"""Device time per round of the round program (``fl/engine.py``
``round_fn``: local phase, fuse, server step), from its XLA module in the
trace; the mean over the cell's chips."""
from bench.trace import matching


def read(ctx):
    per_chip = [matching(d["module_ns"], {}, ("round_fn",))
                for d in ctx.summary["devices"].values()]
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / ctx.rounds / 1e6
