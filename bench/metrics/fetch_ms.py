"""Host time per round in the benchmark's ``fetch`` span: the loader
call (``get_batch``) that ``run_federated`` packs each client step's
batch with (host runtime layer)."""


def read(ctx):
    ns = ctx.summary["host_ns"].get("fetch")
    return None if not ns else ns / ctx.rounds / 1e6
