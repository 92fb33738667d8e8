"""Device time per round of the expert layers: the union of the intervals
of the ops whose name stack holds the model's ``moe`` scope
(``models/moe.py`` ``moe_apply``: routing, the held experts' grouped
products and the shared experts, forward, recomputation and backward
inside the round's local phase); the mean over the chips."""
from bench import model_scopes


def read(ctx):
    return model_scopes.ms_per_round(ctx, "moe")
