"""Host time per round inside the program's ``fl.pack`` spans
(``repro.fl.runtime.pad_tile_inputs``): each engine tile's padding and
weights, its ``get_batch`` calls (``fl.load``) and its batch stacks and
host-to-device copies (``fl.stack``) (host runtime layer)."""
from bench import spans


def read(ctx):
    s = spans.of(ctx)
    ns = s["program_ns"].get("fl.pack") if s else None
    return ns / ctx.rounds / 1e6 if ns else None
