"""Share of the traced window in which the device sat idle while the host
was in the service layer (queueing, cache, batch pricing, coalescing,
completion and stats), in percent.

Each idle gap of the cell's first chip is named by the innermost program
span over it (``bench/tracing.py``); this counts the gaps named by a
span of the service, ``service.*``.  With the four other ``idle_*_share``
readers and the client's remainder (``bench.*`` and ``none``) it splits
``device_idle_share`` on one chip.  Moves queries_per_s.
"""


def counts(name: str) -> bool:
    return name.startswith("service.")


def read(ctx):
    red = ctx["trace"]
    if red is None or red.n_devices == 0 or red.window_s <= 0:
        return None
    return 100.0 * sum(s for n, s in red.gaps if counts(n)) / red.window_s
