"""Share of the traced window in which no operation ran on the device.

1 - (union of the device's ``XLA Ops`` intervals / window), averaged over
the chips the cell uses, in percent.  Split by the end-to-end metric it
moves: ``.readmap`` moves queries_per_s.
"""


def read(ctx):
    red = ctx["trace"]
    if red is None or red.n_devices == 0 or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
