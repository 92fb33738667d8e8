"""Device time per round of the fusion step: the union of the intervals
of the ops whose name stack holds the ``fuse`` scope (``fl/engine.py``:
the robust pre-step and the method's fuse, for Fed2 the Pallas
``paired_fusion`` kernel with its padding and copies); the mean over the
chips."""
from bench import spans


def read(ctx):
    s = spans.of(ctx)
    ns = spans.mean_over_chips(s, "scope_ns", "fuse") if s else 0
    return ns / ctx.rounds / 1e6 if ns else None
