"""Share of the traced window in which no operation ran on the chip:
one minus the union of the chip's operation intervals over the window,
averaged over the chips the cell uses."""

LAYER = "device"
UNIT = "%"
MOVES = "search_s"


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.mean_busy_s() / tr.window_s)
