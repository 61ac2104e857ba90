"""Mean share of rows the q-gram filter passed to verification, in
percent, over the window's filtered launches (``MatchResult.
survivor_frac`` of each launch)."""


def read(ctx):
    fr = [l["survivor_frac"] for l in ctx["window"].launches
          if l["strategy"] == "filter" and l["survivor_frac"] is not None]
    return 100.0 * sum(fr) / len(fr) if fr else None
