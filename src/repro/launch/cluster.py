"""Multi-host cluster bootstrap (1000+ node path).

On a real TPU/TRN fleet every host runs the same entry point; this module
derives (coordinator, process_id, process_count) from the scheduler
environment (TPU metadata, SLURM, or explicit REPRO_* variables), calls
``jax.distributed.initialize``, and returns the host's role.  The rest of
the stack is already multi-host-clean:

* ``make_production_mesh`` builds from ``jax.devices()`` (global after
  initialize);
* ``data.pipeline.host_shard`` slices the deterministic batch stream by
  (process_id, process_count) -- restarts replay identically on any host
  count;
* ``checkpoint.CheckpointManager`` restores onto any mesh (elastic), so a
  job rescheduled from 2 pods to 1 resumes from the same step;
* the straggler watchdog (runtime/loop.py) triggers the snapshot +
  drop-and-reshard path on slow hosts.

Typical driver::

    from repro.launch import cluster
    info = cluster.initialize()           # no-op on a single host
    mesh = make_production_mesh(multi_pod=info.process_count > 1)

The module doubles as a runnable multi-controller proof (DESIGN.md
Sec. 3k): ``python -m repro.launch.cluster --demo`` spawns a 2-process
CPU ``jax.distributed`` job (4 forced host devices each -> the same
8-shard mesh a single process gets) plus a 1-process 8-shard baseline,
runs the full match workload -- threshold / forced-filter / IUPAC
wildcard / top-k / best, then ``append_rows`` growth, tombstoning, and
``compact()`` -- in every process, and asserts the results are
bit-identical across the two layouts with flat per-host pack counters.
"""

from __future__ import annotations

import dataclasses
import json
import os
import socket
import subprocess
import sys
import tempfile
from pathlib import Path
from typing import Dict, List, Optional


@dataclasses.dataclass(frozen=True)
class HostInfo:
    coordinator: Optional[str]
    process_id: int
    process_count: int

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0


def detect_environment(env=None) -> HostInfo:
    """Resolve the host's role from the environment (no side effects).

    Priority: explicit REPRO_* vars > SLURM > single host.
    """
    env = env if env is not None else os.environ
    if "REPRO_COORDINATOR" in env:
        return HostInfo(
            coordinator=env["REPRO_COORDINATOR"],
            process_id=int(env.get("REPRO_PROCESS_ID", "0")),
            process_count=int(env.get("REPRO_NUM_PROCESSES", "1")),
        )
    if "SLURM_JOB_NUM_NODES" in env and int(env["SLURM_JOB_NUM_NODES"]) > 1:
        nodelist = env.get("SLURM_STEP_NODELIST", env.get("SLURM_NODELIST", ""))
        first = _first_slurm_node(nodelist)
        port = env.get("REPRO_PORT", "8476")
        return HostInfo(
            coordinator=f"{first}:{port}" if first else None,
            process_id=int(env.get("SLURM_PROCID", "0")),
            process_count=int(env["SLURM_JOB_NUM_NODES"]),
        )
    return HostInfo(coordinator=None, process_id=0, process_count=1)


def _first_slurm_node(nodelist: str) -> Optional[str]:
    """First hostname of a SLURM nodelist ('a[001-004],b02' -> 'a001')."""
    if not nodelist:
        return None
    head = nodelist.split(",")[0]
    if "[" not in head:
        return head
    prefix, rng = head.split("[", 1)
    rng = rng.rstrip("]")
    first = rng.split(",")[0].split("-")[0]
    return prefix + first


def initialize(info: Optional[HostInfo] = None) -> HostInfo:
    """Call jax.distributed.initialize when running multi-host; no-op on a
    single host (this container)."""
    info = info or detect_environment()
    if info.process_count > 1 and info.coordinator:
        import jax
        if "cpu" in os.environ.get("JAX_PLATFORMS", ""):
            # CPU multi-controller needs the gloo collectives backend;
            # the default CPU client refuses cross-process collectives.
            # Must be set before jax.distributed.initialize.
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=info.coordinator,
            num_processes=info.process_count,
            process_id=info.process_id,
        )
    return info


# -- multi-process CPU demo (DESIGN.md Sec. 3k bit-identity gate) ------------

def cpu_process_env(process_id: int, num_processes: int, coordinator: str,
                    local_devices: int = 4) -> Dict[str, str]:
    """Environment overrides for one CPU process of a local multi-
    controller job: ``local_devices`` forced host devices per process,
    role wired through the REPRO_* variables ``detect_environment``
    reads."""
    return {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count="
                     f"{int(local_devices)}",
        "REPRO_COORDINATOR": coordinator,
        "REPRO_PROCESS_ID": str(int(process_id)),
        "REPRO_NUM_PROCESSES": str(int(num_processes)),
    }


def _demo_workload() -> dict:
    """The deterministic match workload every demo process runs.

    Same seed, same queries, same mutation sequence in every process --
    the SPMD contract.  Returns a JSON-serializable dict of results
    (reduced outputs only; exactly what crosses the merge layer to the
    host) plus the corpus pack counters, so layouts can be compared
    bit-for-bit.
    """
    import jax
    import numpy as np

    from ..match.corpus import PackedCorpus
    from ..match.engine import MatchEngine
    from ..match.query import MatchQuery
    from .mesh import make_row_mesh

    n_dev = len(jax.devices())
    mesh = make_row_mesh(n_dev) if n_dev > 1 else None
    rng = np.random.default_rng(7)
    frags = rng.integers(0, 4, size=(1024, 64)).astype(np.uint8)
    pattern = np.array(frags[11, 10:42])          # 32-char planted needle
    planted = [3, 500, 1021]
    for r in planted:
        frags[r, 5:37] = pattern
    corpus = PackedCorpus(frags, capacity=2048)
    # record_runtimes off even single-process: feedback re-pricing could
    # flip a later plan in the baseline but not the (always-off)
    # multi-controller run, breaking the apples-to-apples comparison.
    engine = MatchEngine(corpus, mesh=mesh, record_runtimes=False)

    iupac = "".join("ACGT"[c] for c in pattern)
    iupac = iupac[:2] + "N" + iupac[3:17] + "N" + iupac[18:]
    thr = float(pattern.size)
    queries = {
        "threshold_scan": MatchQuery.exact(
            pattern, reduction="threshold", threshold=thr, filter=False),
        "threshold_filtered": MatchQuery.exact(
            pattern, reduction="threshold", threshold=thr, filter=True),
        "iupac_wildcard": MatchQuery.iupac(
            iupac, reduction="threshold", threshold=thr),
        "topk": MatchQuery.exact(pattern, reduction="topk", k=9),
        "best": MatchQuery.exact(pattern),
    }
    compiled = {name: engine.compile(q) for name, q in queries.items()}

    def snap(res) -> dict:
        out = {
            "merge_path": res.merge_path,
            "collective_bytes": int(res.collective_bytes),
            "n_shards": int(res.n_shards),
            "backend": res.plan.backend,
            "strategy": res.plan.strategy,
            "best_locs": np.asarray(res.best_locs).tolist(),
            "best_scores": np.asarray(res.best_scores).tolist(),
        }
        if res.hits is not None:
            out["hits"] = np.asarray(res.hits).tolist()
        if res.topk_rows is not None:
            out["topk_rows"] = np.asarray(res.topk_rows).tolist()
            out["topk_scores"] = np.asarray(res.topk_scores).tolist()
        if res.survivor_rows is not None:
            out["n_survivors"] = int(np.asarray(res.survivor_rows).size)
        return out

    results = {name: snap(c.run()) for name, c in compiled.items()}
    base_expect = {(3, 5), (500, 5), (1021, 5), (11, 10)}
    for stage in ("threshold_scan", "threshold_filtered"):
        got0 = {(int(r), int(l)) for r, l, _ in results[stage]["hits"]}
        if base_expect - got0:
            raise AssertionError(
                f"{stage}: planted rows missing: "
                f"{sorted(base_expect - got0)} (got {sorted(got0)})")

    # Growth: 96 appended rows with the needle planted in one of them
    # (logical row 1024 + 40); the splice must land it on the right
    # shard under the cyclic layout in every process.
    extra = np.random.default_rng(11).integers(
        0, 4, size=(96, 64)).astype(np.uint8)
    extra[40, 20:52] = pattern
    corpus.append_rows(extra)
    results["threshold_after_append"] = snap(compiled["threshold_scan"].run())
    results["topk_after_append"] = snap(compiled["topk"].run())

    # Eviction: tombstone two planted rows (their hits must vanish),
    # then compact (ids above the dead rows shift down by two).
    corpus.tombstone([3, 500])
    results["threshold_after_tombstone"] = snap(
        compiled["threshold_scan"].run())
    corpus.compact()
    results["threshold_after_compact"] = snap(
        compiled["threshold_scan"].run())
    results["best_after_compact"] = snap(compiled["best"].run())

    # Zero-false-negative gate, independent of any cross-layout diff:
    # every surviving planted row must report an exact-score hit.
    expect = {(11 - 1, 10), (1021 - 2, 5), (1024 + 40 - 2, 20)}
    got = {(int(r), int(l)) for r, l, _ in
           results["threshold_after_compact"]["hits"]}
    missing = expect - got
    if missing:
        raise AssertionError(
            f"planted rows missing from threshold hits: {sorted(missing)} "
            f"(got {sorted(got)})")

    return {
        "process_count": jax.process_count(),
        "process_id": jax.process_index(),
        "n_devices": n_dev,
        "n_shards": engine._row_shards,
        "merge_path": engine.merger.merge_path,
        "collective_bytes": int(engine.merger.collective_bytes),
        "n_collectives": int(engine.merger.n_collectives),
        "pack_counts": {
            "swar": corpus.swar_pack_count,
            "onehot": corpus.onehot_pack_count,
            "host_total": corpus.host_pack_count,
            "row_updates": corpus.row_update_count,
        },
        "results": results,
    }


def _worker_main() -> None:
    """Entry point for one demo process (spawned by ``run_cpu_demo``)."""
    info = initialize()
    summary = _demo_workload()
    out = os.environ.get("REPRO_DEMO_OUT")
    if out:
        with open(out, "w") as fh:
            json.dump(summary, fh, indent=2, sort_keys=True)
    if info.is_coordinator:
        print(json.dumps({k: summary[k] for k in
                          ("process_count", "n_shards", "merge_path",
                           "collective_bytes", "pack_counts")}))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_worker(env_over: Dict[str, str], out_path: str,
                  extra_env: Optional[Dict[str, str]] = None):
    env = dict(os.environ)
    for k in ("REPRO_COORDINATOR", "REPRO_PROCESS_ID",
              "REPRO_NUM_PROCESSES", "REPRO_DEMO_OUT"):
        env.pop(k, None)
    src = str(Path(__file__).resolve().parents[2])
    pp = env.get("PYTHONPATH")
    env["PYTHONPATH"] = src + (os.pathsep + pp if pp else "")
    env.update(env_over)
    env["REPRO_DEMO_OUT"] = out_path
    if extra_env:
        env.update(extra_env)
    return subprocess.Popen(
        [sys.executable, "-m", "repro.launch.cluster", "--worker"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def run_cpu_demo(n_processes: int = 2, local_devices: int = 4,
                 timeout: float = 600.0) -> dict:
    """Run the bit-identity gate: ``n_processes`` CPU controllers
    (``local_devices`` forced host devices each) vs a single process
    with the same global device count, same 8-shard mesh.

    Returns a summary dict with per-layout results and the list of
    mismatching stages (empty == gate passed).  Raises RuntimeError if
    any worker exits non-zero.
    """
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    tmp = tempfile.mkdtemp(prefix="repro_mh_demo_")
    outs = [os.path.join(tmp, f"proc{i}.json") for i in range(n_processes)]
    base_out = os.path.join(tmp, "single.json")
    procs = [
        _spawn_worker(cpu_process_env(i, n_processes, coord, local_devices),
                      outs[i])
        for i in range(n_processes)
    ]
    # Single-process baseline: same global device count, no distributed
    # init (REPRO_COORDINATOR unset -> process_count == 1).
    procs.append(_spawn_worker(
        {"JAX_PLATFORMS": "cpu",
         "XLA_FLAGS": f"--xla_force_host_platform_device_count="
                      f"{n_processes * local_devices}"},
        base_out))
    failures: List[str] = []
    for i, p in enumerate(procs):
        try:
            stdout, stderr = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise RuntimeError(
                f"demo worker {i} timed out after {timeout}s")
        if p.returncode != 0:
            tag = "baseline" if i == n_processes else f"proc{i}"
            failures.append(
                f"[{tag}] exit {p.returncode}\n{stderr[-4000:]}")
    if failures:
        raise RuntimeError("demo workers failed:\n" + "\n".join(failures))
    multi = [json.load(open(o)) for o in outs]
    single = json.load(open(base_out))

    mismatches: List[str] = []
    for i in range(1, n_processes):
        if multi[i]["results"] != multi[0]["results"]:
            mismatches.append(f"proc{i} diverged from proc0 (SPMD break)")

    def strip(stage: dict) -> dict:
        # Byte accounting legitimately depends on the controller
        # topology (a single controller addresses every shard directly;
        # a multi-controller gather is a collective) -- compare the
        # *results*, not the transfer ledger.
        return {k: v for k, v in stage.items() if k != "collective_bytes"}

    for stage in single["results"]:
        if (strip(multi[0]["results"].get(stage, {}))
                != strip(single["results"][stage])):
            mismatches.append(stage)
    if single["pack_counts"] != multi[0]["pack_counts"]:
        mismatches.append(
            f"pack_counts: single={single['pack_counts']} "
            f"multi={multi[0]['pack_counts']}")
    return {
        "identical": not mismatches,
        "mismatches": mismatches,
        "n_processes": n_processes,
        "local_devices": local_devices,
        "n_shards": multi[0]["n_shards"],
        "multiprocess": multi,
        "single": single,
    }


def _main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worker", action="store_true",
                    help="run one demo process (internal; spawned by "
                         "--demo)")
    ap.add_argument("--demo", action="store_true",
                    help="run the 2-process CPU bit-identity demo")
    ap.add_argument("--processes", type=int, default=2)
    ap.add_argument("--local-devices", type=int, default=4)
    args = ap.parse_args(argv)
    if args.worker:
        _worker_main()
        return 0
    if args.demo:
        summary = run_cpu_demo(args.processes, args.local_devices)
        print(json.dumps(
            {k: summary[k] for k in ("identical", "mismatches",
                                     "n_processes", "n_shards")},
            indent=2))
        return 0 if summary["identical"] else 1
    ap.print_help()
    return 2


if __name__ == "__main__":
    raise SystemExit(_main())
