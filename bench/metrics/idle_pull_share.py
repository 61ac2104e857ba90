"""Share of the traced window in which the device sat idle while the host
pulled a result to host memory, in percent.

Each idle gap of the cell's first chip is named by the innermost program
span over it (``bench/tracing.py``); this counts the gaps named in
``SPANS``: ``pull.wait`` (the value still computing, so rarely idle),
``pull.copy`` (the device-to-host copy and host un-permute) and ``pull``
(what neither covers).  One of the five ``idle_*_share`` readers that
split ``device_idle_share``.  Moves queries_per_s.
"""

SPANS = frozenset({"pull", "pull.wait", "pull.copy"})


def counts(name: str) -> bool:
    return name in SPANS


def read(ctx):
    red = ctx["trace"]
    if red is None or red.n_devices == 0 or red.window_s <= 0:
        return None
    return 100.0 * sum(s for n, s in red.gaps if counts(n)) / red.window_s
