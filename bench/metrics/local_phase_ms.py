"""Device time per round of the round program's local phase: the union
of the intervals of the ops whose name stack holds the ``local`` scope
(``fl/engine.py``: broadcast and the vmapped client updates); the mean
over the chips."""
from bench import spans


def read(ctx):
    s = spans.of(ctx)
    ns = spans.mean_over_chips(s, "scope_ns", "local") if s else 0
    return ns / ctx.rounds / 1e6 if ns else None
