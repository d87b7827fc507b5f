# One function per paper table. Print ``name,us_per_call,derived`` CSV.
from __future__ import annotations

import argparse
import sys
import time

# Named single benches runnable via ``--bench`` (JSON emitters included).
BENCHES = ("megakernel", "kernels", "iterations", "sample_size", "topology",
           "flips", "realworld", "theory", "lambda_path",
           "fit_serving", "node_virtual")


def _run_one(name: str) -> None:
    import importlib
    mod = importlib.import_module(f"benchmarks.bench_{name}")
    mod.run()
    _validate_artifact(name)


def _validate_artifact(name: str) -> None:
    """Validate the bench's BENCH_<name>.json (if it emits one) against
    the shared schema, so a bench refactor cannot silently drop the
    fields the acceptance gates read."""
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    artifact = root / f"BENCH_{name}.json"
    if not artifact.exists():
        return                       # CSV-only bench
    sys.path.insert(0, str(root))    # tools/ may not be importable yet
    try:
        from tools.declint.bench_schema import validate_file
    finally:
        sys.path.pop(0)
    problems = validate_file(artifact)
    if problems:
        for p in problems:
            print(f"{artifact.name}: {p}", file=sys.stderr)
        raise SystemExit(f"{artifact.name} violates the BENCH schema "
                         f"(tools/declint/bench_schema.py)")
    print(f"# {artifact.name}: schema ok", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--bench", choices=BENCHES, default=None,
                    help="run a single named benchmark instead of the suite")
    args = ap.parse_args()
    if args.bench is not None:
        _run_one(args.bench)
        return
    print("name,us_per_call,derived")
    t0 = time.time()
    from benchmarks import (bench_flips, bench_iterations, bench_kernels,
                            bench_realworld, bench_sample_size, bench_theory,
                            bench_topology, roofline)
    bench_iterations.run()       # paper Figure 1
    bench_sample_size.run()      # paper Tables 1-2
    bench_topology.run()         # paper Tables 3-4
    bench_flips.run()            # paper Table 5
    bench_realworld.run()        # paper Table 6 (offline analogue)
    bench_theory.run()           # Theorems 1 & 2 direct checks
    bench_kernels.run()          # Pallas hot-spot microbench
    try:
        roofline.run()           # deliverable (g), from dry-run JSONs
    except Exception as e:  # noqa: BLE001 — dry-run results may be absent
        print(f"roofline/skipped,0.0,reason={e!r}", file=sys.stderr)
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == '__main__':
    main()
