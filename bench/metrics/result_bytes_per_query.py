"""Bytes of result arrays handed back per answered query: every array a
``MatchResult`` carries to the caller (per-row bests, top-k, hits,
survivor ids), summed over the window's answers, over their count."""


def read(ctx):
    win = ctx["window"]
    return win.result_bytes / win.answered if win.answered else None
