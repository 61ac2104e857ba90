"""Share of the match kernels' roofline, in percent.

The least time the work could take on the chip, over the summed device
time of the match kernels' events in the traced window.  The work is
counted from the algorithm, never from the kernel: each launch compares
every (row scanned, alignment, pattern) triple at every pattern position,
one compare and one add each, against the int8 peak; or reads the
scanned rows' 2-bit corpus once, against HBM bandwidth; whichever bound
is larger.  A filtered launch scans only its survivor rows.
"""

# HLO instruction names of the match kernels in the device trace
# (``%match_swar.1``, ``%match_swar_masks.1``, ``%match_mxu.1``).
KERNELS = ("%match_swar", "%match_mxu")


def least_seconds(launch, peaks, fragment_chars):
    triples = launch["rows_scanned"] * launch["n_locs"] * \
        launch["n_patterns"]
    ops = triples * launch["pattern_chars"] * 2
    corpus_bytes = launch["rows_scanned"] * fragment_chars / 4
    return max(ops / peaks["int8_ops_per_s"],
               corpus_bytes / peaks["hbm_bytes_per_s"])


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    t = red.seconds_matching(KERNELS)
    launches = ctx["window"].launches
    if t <= 0 or not launches:
        return None
    least = sum(least_seconds(l, ctx["peaks"],
                              ctx["config"]["fragment_chars"])
                for l in launches)
    return 100.0 * least / t
