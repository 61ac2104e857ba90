"""Share of the traced window in which the device sat idle while the host
was handing it work: kernel launches, merge dispatches and q-gram filter
launches, in percent.

Each idle gap of the cell's first chip is named by the innermost program
span over it (``bench/tracing.py``); this counts the gaps named in
``SPANS``.  One of the five ``idle_*_share`` readers that split
``device_idle_share``.  Moves queries_per_s.
"""

SPANS = frozenset({"launch", "merge", "filter.launch"})


def counts(name: str) -> bool:
    return name in SPANS


def read(ctx):
    red = ctx["trace"]
    if red is None or red.n_devices == 0 or red.window_s <= 0:
        return None
    return 100.0 * sum(s for n, s in red.gaps if counts(n)) / red.window_s
