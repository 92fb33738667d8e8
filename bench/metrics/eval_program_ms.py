"""Device time per round of the evaluation engine (``fl/evaluation.py``:
the fused ``counts`` program, or ``one_tile`` and its accumulation on the
host-dispatch path), from its XLA modules; the mean over the chips."""
from bench.trace import matching

EVAL_MODULES = ("jit_counts", "jit_one_tile")


def read(ctx):
    per_chip = [matching(d["module_ns"], {}, EVAL_MODULES)
                for d in ctx.summary["devices"].values()]
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / ctx.rounds / 1e6
