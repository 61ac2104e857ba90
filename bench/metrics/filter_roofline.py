"""Share of the q-gram filter's roofline, in percent.

A filtered launch has to read the resident signature form once: rows x
signature bits / 8 bytes, against HBM bandwidth.  That least time, summed
over the window's filtered launches, over the summed device time of the
filter kernel's events.
"""

# HLO instruction name of the filter kernel (``%filter_qgram.1``).
KERNELS = ("%filter_qgram",)


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    t = red.seconds_matching(KERNELS)
    launches = [l for l in ctx["window"].launches
                if l["strategy"] == "filter"]
    if t <= 0 or not launches:
        return None
    bits = ctx["config"]["filter"]["bits"]
    least = sum(l["n_rows"] * bits / 8 / ctx["peaks"]["hbm_bytes_per_s"]
                for l in launches)
    return 100.0 * least / t
