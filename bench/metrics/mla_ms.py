"""Device time per round of the latent attention: the union of the
intervals of the ops whose name stack holds the model's ``mla`` scope
(``models/attention.py`` ``mla_apply``, its forward, recomputation and
backward inside the round's local phase); the mean over the chips."""
from bench import model_scopes


def read(ctx):
    return model_scopes.ms_per_round(ctx, "mla")
