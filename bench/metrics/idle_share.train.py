"""Share of the traced training window in which no operation ran on the
device: 1 - (union of device op intervals) / window."""
UNIT = "%"


def compute(ctx):
    red = ctx["trace"]
    return 100.0 * (1.0 - red.busy_s / red.window_s)
