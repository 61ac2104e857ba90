# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark driver: ``PYTHONPATH=src python -m benchmarks.run [--only X]``.

Modules (one per paper table/figure + assignment deliverables):
  table1_gates      -- Table 1/3 gate windows + truth tables
  fig5_throughput   -- Fig. 5 Naive/Oracular x Opt throughput/energy
  fig6_breakdown    -- Fig. 6 stage breakdown
  fig7_patlen       -- Fig. 7 pattern-length sensitivity
  fig8_tech         -- Fig. 8 MTJ technology sensitivity
  fig9_10_nmp       -- Figs. 9/10 vs NMP / NMP-Hyp
  fig11_gates       -- Fig. 11 bulk bitwise vs Ambit/Pinatubo
  table4_apps       -- Table 4 benchmark apps
  kernel_bench      -- TPU-adapted kernel engine (beyond paper)
  service_bench     -- multi-tenant match service coalescing (beyond paper)
  query_bench       -- compiled-query reuse + wildcard predicates (beyond)
  ingest_bench      -- online ingestion into a live store (beyond paper)
  filter_bench      -- q-gram filter-then-verify vs full scan (beyond)
  standing_bench    -- fused standing-query bank vs per-pattern loop
  shard_bench       -- mesh-sharded 1M-row scaling sweep (beyond paper)
  calibrate_bench   -- autotuned cost model: the three Sec. 3i proofs
  roofline          -- dry-run roofline table (assignment)

Modules that maintain a committed ``BENCH_*.json`` artifact also print one
``<name>,artifact,<summary>`` line (via their ``artifact_summary`` hook),
so the perf trajectory across PRs is greppable straight from the driver
output (``grep ',artifact,'``).
"""

import argparse
import os
import sys
import traceback

# Forced host devices so shard_bench's mesh sweep works under the driver;
# must land before the first benchmark module imports jax (harmless for
# the others, and on real accelerators the flag only affects the host
# platform).
_FORCE = "--xla_force_host_platform_device_count"
if "jax" not in sys.modules and _FORCE not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" {_FORCE}=8").strip()

MODULES = [
    "table1_gates", "fig5_throughput", "fig6_breakdown", "fig7_patlen",
    "fig8_tech", "fig9_10_nmp", "fig11_gates", "table4_apps",
    "sec5_5_variation", "kernel_bench", "service_bench", "query_bench",
    "ingest_bench", "filter_bench", "standing_bench", "shard_bench",
    "calibrate_bench",
    "roofline",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset")
    args = ap.parse_args()
    mods = args.only.split(",") if args.only else MODULES
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    for name in mods:
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            for row_name, us, derived in mod.run():
                print(f"{row_name},{us},{derived}")
            summary = getattr(mod, "artifact_summary", None)
            if summary is not None:
                line = summary()
                if line:
                    print(f"{name},artifact,{line}")
        except Exception:
            failures += 1
            print(f"{name},ERROR,{traceback.format_exc(limit=1).splitlines()[-1]}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
