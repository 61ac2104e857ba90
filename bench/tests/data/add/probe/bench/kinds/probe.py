"""A probe of the kind contract (``"kind": "probe"``): exact lookups and
one acknowledged ingest through ``MatchService``.

Data: uniform random rows from the seed.  Client: a closed loop of
batches of exact lookups (threshold = pattern length) of windows drawn
from the rows the corpus holds.  After ``ingest_after`` batches it
ingests ``ingest_rows`` new rows and ticks until they are acknowledged;
every later batch also looks up the start of each acknowledged row.  The
window closes at the first batch completion after ``--seconds`` that
follows the acknowledgement.

Check, in NumPy: each sampled lookup against the rows the corpus held
when it was asked (``hits_wrong``), and each acknowledged row found by
the lookup of its own start at its acknowledged row id
(``readback_missing``); ``lost`` and ``no_sample`` as for read mapping.
All limits 0.  Control: answers read from the corpus as it stood before
the ingest, a stale read.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from bench import drive, gen, reference
from bench.spec import SpecError

SPANS = frozenset({"bench.window"})


@dataclasses.dataclass
class Lookup:
    masks: np.ndarray
    n_rows: int                      # rows the corpus held when asked
    hits: Optional[np.ndarray]
    row: int = -1                    # the acknowledged row read back


@dataclasses.dataclass
class Deployment:
    stack: drive.Stack
    rows: np.ndarray                 # every row sent, in row-id order
    config: dict
    traffic: dict
    reads: np.random.Generator
    sample: drive.Reservoir
    note: str
    readback: List[Lookup] = dataclasses.field(default_factory=list)
    acked: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Held:
    rows: np.ndarray
    n_before: int                    # rows before the ingest
    sample: List[Lookup]
    readback: List[Lookup]
    acked: List[int]


def set_up(cs, seed, devices, trace):
    config, traffic = cs["config"], cs["traffic"]
    rows = gen.rng(seed, gen.GENOME).integers(
        0, 4, (config["rows"], config["fragment_chars"]), dtype=np.uint8)
    stack = drive.build_stack(config, rows, devices, trace)
    dep = Deployment(stack, rows, config, traffic, gen.rng(seed, gen.READS),
                     drive.Reservoir(traffic["check_sample"],
                                     gen.rng(seed, gen.SAMPLE)), "")
    warm = gen.rng(seed, gen.WARMUP)
    for _ in range(traffic["warmup_batches"]):
        drive.serve(stack.service, [_query(dep, m)
                                    for m in _draws(dep, warm)])
    dep.note = (f"{rows.shape[0]} rows x {rows.shape[1]} chars, "
                f"{traffic['warmup_batches']} warm-up batches")
    return dep


def _draws(dep, r) -> List[np.ndarray]:
    P, F = dep.config["pattern_chars"], dep.config["fragment_chars"]
    rows = r.integers(0, dep.rows.shape[0], dep.traffic["batch"])
    locs = r.integers(0, F - P + 1, dep.traffic["batch"])
    return [gen.exact_masks(dep.rows[i, l:l + P]) for i, l in
            zip(rows, locs)]


def _query(dep, masks):
    return drive.make_query(masks, {"reduction": "threshold",
                                    "threshold": masks.size})


def window(dep, seconds, trace):
    svc, tr, P = dep.stack.service, dep.traffic, dep.config["pattern_chars"]
    win = drive.Window()
    t0 = drive.clock()
    batches = 0
    with drive.annotate("bench.window", trace):
        while True:
            if batches == tr["ingest_after"]:
                new = dep.reads.integers(
                    0, 4, (tr["ingest_rows"], dep.rows.shape[1]),
                    dtype=np.uint8)
                win.attempted += 1
                tk = svc.ingest(new)
                for _ in range(4):
                    if tk.done:
                        break
                    svc.tick()
                if tk.done:
                    win.completed += 1
                    dep.acked = list(range(tk.start, tk.start + len(new)))
                    dep.rows = np.concatenate([dep.rows, new])
                else:
                    win.failed += 1
                    win.errors.append("ingest not acknowledged")
            draws = _draws(dep, dep.reads)
            back = [gen.exact_masks(dep.rows[i, :P]) for i in dep.acked]
            t_sub = drive.clock()
            tickets, n = drive.serve(svc, [_query(dep, m)
                                           for m in draws + back])
            win.n_ticks += n
            t = drive.clock()
            win.tick_s.append(t - t_sub)
            done = []
            for i, (m, tk) in enumerate(zip(draws + back, tickets)):
                win.attempted += 1
                if not tk.done or tk.error is not None:
                    win.failed += 1
                    win.errors.append(repr(tk.error))
                    continue
                win.completed += 1
                done.append((tk.result, tk.cached))
                got = Lookup(m, dep.rows.shape[0], tk.result.hits)
                if i < len(draws):
                    dep.sample.offer(lambda got=got: got)
                else:
                    got.row = dep.acked[i - len(draws)]
                    dep.readback.append(got)
            drive.note_results(win, done)
            batches += 1
            if t - t0 >= seconds and batches > tr["ingest_after"]:
                break
    win.seconds = t - t0
    return win


def metric(name, win):
    if name == "queries_per_s":
        return win.completed / win.seconds
    raise SpecError(f"no measurement for {name!r}")


def release(dep):
    return Held(dep.rows, dep.rows.shape[0] - len(dep.acked),
                dep.sample.items, dep.readback, dep.acked)


def _hits(rows, masks) -> set:
    sc = reference.scores_np(rows, masks[None])[:, :, 0]
    r, loc = np.nonzero(sc >= masks.size)
    return {(int(a), int(b), int(sc[a, b])) for a, b in zip(r, loc)}


def _got(h) -> set:
    if h is None:
        return set()
    return {tuple(int(x) for x in t) for t in np.asarray(h).reshape(-1, 3)}


def _numbers(held, win) -> Dict[str, dict]:
    wrong = sum(_got(s.hits) != _hits(held.rows[:s.n_rows], s.masks)
                for s in held.sample)
    missing = sum((s.row, 0, s.masks.size) not in _got(s.hits)
                  for s in held.readback)
    missing += len(set(held.acked) - {s.row for s in held.readback})
    vals = {"lost": win.failed, "hits_wrong": wrong,
            "readback_missing": missing, "no_sample": int(not held.sample)}
    return {k: {"value": int(v), "limit": 0} for k, v in vals.items()}


def check(held, win):
    return (_numbers(held, win),
            f"{len(held.sample)} sampled lookups, {len(held.readback)} "
            f"read-backs of {len(held.acked)} acknowledged rows")


def control(held, win):
    old = held.rows[:held.n_before]
    stale = [dataclasses.replace(s, hits=np.array(
        sorted(_hits(old, s.masks)), np.int64).reshape(-1, 3))
        for s in held.readback]
    return _numbers(dataclasses.replace(held, readback=stale), win)
