"""Share of the traced window in which the device is idle while the host
is inside the program's ``fl.pack`` span, at any depth: the device
waiting for a tile's inputs; the mean over the chips."""
from bench import spans


def read(ctx):
    s = spans.of(ctx)
    if not s or "fl.pack" not in s["program_ns"]:
        return None
    idle = spans.mean_over_chips(s, "idle_under_program_ns", "fl.pack")
    return 100.0 * idle / s["window_ns"]
