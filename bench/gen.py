"""The one general generator: corpus and traffic made from ``--seed``.

A configuration file gives the deployment's sizes (genome length, row
width and stride), a traffic file the mix (batch, reduction, errors).
Everything here is NumPy and depends on nothing but those two files and
the seed, so the same seed gives the same rows and reads on every
machine.

Where a mix lists its error counts, every seed gets the same counts in
another order, so a seed changes which rows are asked for, not how much
work a run holds.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List

import numpy as np

# Independent random streams of one seed.
GENOME, READS, SAMPLE, WARMUP = 0, 1, 5, 6


def rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one stream of one seed (any whole number)."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64), stream]))


def n_rows(genome_bp: int, fragment_chars: int, stride: int) -> int:
    """Rows of width ``fragment_chars`` every ``stride`` chars that hold
    every window of ``fragment_chars - stride + 1`` chars wholly."""
    overlap = fragment_chars - stride
    return max(1, -(-(genome_bp - overlap) // stride))


def genome(seed: int, genome_bp: int, fragment_chars: int,
           stride: int) -> np.ndarray:
    """Uniform random bases, padded so the last row is whole.

    The padding continues the random sequence; reads are drawn from the
    first ``genome_bp`` bases only.
    """
    rows = n_rows(genome_bp, fragment_chars, stride)
    total = max(genome_bp, (rows - 1) * stride + fragment_chars)
    return rng(seed, GENOME).integers(0, 4, total, dtype=np.uint8)


def fold(g: np.ndarray, genome_bp: int, fragment_chars: int,
         stride: int) -> np.ndarray:
    """(rows, fragment_chars) copy of overlapping windows of ``g``."""
    rows = n_rows(genome_bp, fragment_chars, stride)
    view = np.lib.stride_tricks.as_strided(
        g, shape=(rows, fragment_chars), strides=(stride, 1),
        writeable=False)
    return np.ascontiguousarray(view)


def exact_masks(codes: np.ndarray) -> np.ndarray:
    """uint8 one-hot accept masks of base codes."""
    return (np.uint8(1) << np.asarray(codes, np.uint8)).astype(np.uint8)


def substitute(r: np.random.Generator, read: np.ndarray, n_err: int
               ) -> np.ndarray:
    """``read`` with ``n_err`` substitutions at distinct positions."""
    out = read.copy()
    if n_err:
        pos = r.choice(read.size, n_err, replace=False)
        out[pos] = (out[pos] + r.integers(1, 4, n_err)) % 4
    return out


def fixed_counts(r: np.random.Generator, shares: Dict[str, float],
                 n: int) -> List[str]:
    """n labels in exactly the given shares (largest remainder), shuffled."""
    names = list(shares)
    raw = np.array([shares[k] for k in names], float) * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts))[:n - counts.sum()]:
        counts[i] += 1
    labels = np.repeat(np.array(names, object), counts)
    return list(r.permutation(labels))


@dataclasses.dataclass
class Read:
    masks: np.ndarray        # (P,) uint8 accept masks
    origin: int              # genome position, -1 for a random read
    n_err: int


def read_stream(seed: int, g: np.ndarray, genome_bp: int, P: int,
                traffic: dict, stream: int = READS) -> Iterator[Read]:
    """Endless reads drawn from the genome with the traffic's errors.

    ``errors.kind`` is ``binomial`` (Binomial(P, p) substitutions) or
    ``cycle`` (each block of ``block`` reads holds the listed counts in
    equal shares, ``random_share`` of the block being random reads that
    map nowhere).
    """
    r = rng(seed, stream)
    err = traffic["errors"]
    while True:
        if err["kind"] == "binomial":
            kinds = [int(x) for x in r.binomial(P, err["p"], 64)]
        else:
            shares = {"random": traffic.get("random_share", 0.0)}
            rest = 1.0 - shares["random"]
            for c in err["counts"]:
                shares[str(c)] = rest / len(err["counts"])
            kinds = fixed_counts(r, shares, err["block"])
        for kind in kinds:
            if kind == "random":
                yield Read(exact_masks(r.integers(0, 4, P, dtype=np.uint8)),
                           -1, -1)
                continue
            pos = int(r.integers(0, genome_bp - P + 1))
            read = substitute(r, g[pos:pos + P], int(kind))
            yield Read(exact_masks(read), pos, int(kind))
