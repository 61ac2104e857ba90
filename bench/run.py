#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell, its configuration, its traffic and the configuration's kind of
deployment are found by name (``bench/spec.py``).  The kind
(``bench/kinds/<kind>.py``) makes the data and the traffic from the seed,
builds the deployment (resident corpus, q-gram index, engine, service) and
warms the cell's own shapes; then its clients run the window for
``--seconds``.  Afterwards the program's state is freed and the kind checks
the sampled answers against the plain reference on the same chip.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the JAX profiler and the result
carries the per-layer metrics, read by ``bench/metrics/<metric>.py``.
Earlier lines report plans, set-up and the service time per batch; the
last lines on standard error are the compared numbers with their limits, and
the last line on standard output is one JSON object.  Without a TPU (or
with fewer chips than the cell asks for, or a chip missing from
``bench/peaks.json``), or without the program's sources beside the
benchmark, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

if __package__ in (None, ""):
    # Run as a script: import the benchmark as the package ``bench``.
    sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench import drive, spec, tracing  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def look_for_chip(chips: int, root: Path):
    """The cell's TPU devices and their peaks; anything else is refused."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise spec.SpecError(f"no TPU: JAX's backend is {devs[0].platform}; "
                             "the benchmark never falls back to the CPU")
    if len(devs) < chips:
        raise spec.SpecError(f"the cell needs {chips} chips, JAX finds "
                             f"{len(devs)}")
    return devs[:chips], spec.peaks(devs[0].device_kind, root)


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    holding every program, so only a checkout's first run compiles."""
    import jax
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Backend compilations since ``mark()``."""

    def __init__(self):
        import jax
        self.n = 0

        def on(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(on)
        self.n0 = 0

    def mark(self) -> None:
        self.n0 = self.n

    @property
    def since(self) -> int:
        return self.n - self.n0


STATS = ("n_submitted", "n_completed", "n_cache_hits", "n_launches",
         "n_coalesced_launches", "n_filtered_launches", "n_failed",
         "n_ingested_rows", "n_ticks")


def stats_of(service) -> dict:
    s = service.stats
    return {k: getattr(s, k) for k in STATS}


def describe_plans(win: drive.Window) -> None:
    seen = {}
    for l in win.launches:
        key = tuple((k, l[k]) for k in ("backend", "predicate", "mode",
                                        "strategy", "n_patterns",
                                        "chunk_rows", "cost_source"))
        seen[key] = seen.get(key, 0) + 1
    for key, n in sorted(seen.items(), key=lambda kv: -kv[1]):
        log("plan: " + " ".join(f"{k}={v}" for k, v in key)
            + f" launches={n}")


def is_correct(nums: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in nums.values())


def run_cell(cs: dict, seed: int, seconds: float, trace: bool, devices,
             peaks: dict, control: bool = False,
             t_start: float = None) -> dict:
    """Set up, run the window, check, and reduce one run of a cell.

    The cell's kind (``cs["kind"]``, ``bench/spec.py``) sets up, drives
    the window and checks; ``control`` also checks the control's answers
    in the program's place (``bench/control.py``).
    """
    import jax

    kind = cs["kind"]
    t0 = time.perf_counter() if t_start is None else t_start
    counter = CompileCounter()
    dep = kind.set_up(cs, seed, devices, trace)
    log(f"set-up: {dep.note}; {counter.n} backend compiles")
    stack = dep.stack
    stats0 = stats_of(stack.service)
    tmp = tempfile.TemporaryDirectory() if trace else None
    setup_s = time.perf_counter() - t0
    counter.mark()
    with (tracing.capture(tmp.name) if trace
          else contextlib.nullcontext()):
        win = kind.window(dep, seconds, trace)
    compiles = counter.since
    stats1 = stats_of(stack.service)
    delta = {k: stats1[k] - stats0[k] for k in STATS}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    describe_plans(win)
    log(f"window: {win.seconds:.6f} s, {win.attempted} requests, "
        f"{win.completed} completed, {win.failed} failed, {win.n_ticks} "
        f"ticks, {compiles} backend compiles; service deltas {delta}")
    ts = sorted(win.tick_s)
    log(f"service time per batch: min {ts[0]:.6f} s, median "
        f"{statistics.median(ts):.6f} s, max {ts[-1]:.6f} s")
    if win.errors:
        log(f"first error: {win.errors[0]}")

    metrics = {}
    if not trace:
        for m in cs["end_to_end"]:
            name = m["name"]
            v = setup_s if name == "setup_s" else kind.metric(name, win)
            metrics[name] = {"value": v, "unit": m["unit"]}

    # The spans the program recorded, by name, to name the idle gaps.
    spans = {sp.name for sp in stack.engine.obs.tracer.iter_spans()}
    # Free the program's state before the reference runs on the chip.
    held = kind.release(dep)
    del stack, dep
    gc.collect()
    t_ref = time.perf_counter()
    nums, note = kind.check(held, win)
    log(f"reference: {note} checked in {time.perf_counter() - t_ref:.3f} s")
    out = {"correct": is_correct(nums), "attempted": win.attempted,
           "failed": win.failed, "metrics": metrics,
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": int(peak)}}
    if control:
        out["control"] = kind.control(held, win)
    if trace:
        red = tracing.reduce(tracing.load(tmp.name),
                             span_names=spans | set(kind.SPANS),
                             device_ids={d.id for d in devices})
        tmp.cleanup()
        ctx = {"trace": red, "window": win, "stats": delta,
               "compiles": compiles, "config": cs["config"],
               "traffic": cs["traffic"], "peaks": peaks}
        readers = spec.readers(cs["per_layer"])
        for m in cs["per_layer"]:
            v = readers[m["name"]](ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        out["breakdown"] = tracing.breakdown(red)
    out["checks"] = nums
    return out


def main(argv=None, root: Path = spec.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cs = spec.load_cell(args.workload, root)
    except spec.SpecError as e:
        log(f"bench: {e}")
        return 2
    src = root / "src"
    if not (src / "repro" / "match").is_dir():
        log(f"bench: {src} holds no repro package; run from a checkout "
            "of the repository")
        return 2
    sys.path.insert(0, str(src))
    log(f"compile cache: {use_compile_cache(root)}")
    try:
        devices, peaks = look_for_chip(cs["cell"]["chips"], root)
    except spec.SpecError as e:
        log(f"bench: {e}")
        return 1
    log(f"devices: {len(devices)} x {devices[0].device_kind!r}")
    out = run_cell(cs, args.seed, args.seconds, bool(args.trace), devices,
                   peaks, t_start=T_START)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
