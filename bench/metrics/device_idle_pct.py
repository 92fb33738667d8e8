"""Share of the traced window in which no op ran on the device: one less
the union of op intervals over the window, the mean over the chips."""


def read(ctx):
    devs = ctx.summary["devices"].values()
    busy = sum(d["busy_ns"] for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / ctx.summary["window_ns"])
