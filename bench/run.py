"""Run one cell of the benchmark on the accelerator of this machine.

    python3 bench/run.py --workload paper41_p500.path --seed 7 \
        --seconds 45 --trace 0

The cells, their configurations, traffic, metrics and limits are named in
``BENCHMARK.json`` at the root of the checkout.  The last line of stdout is
one JSON object: correct, attempted, failed, metrics, device (and, with
``--trace 1``, breakdown), then the numbers of the check beside their
limits, which are also the last lines of stderr.  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 3.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CACHE = ROOT / ".jax_cache"

# The load is one client in one process: the host's share of a request is a
# few small numpy products, which run on one thread each rather than on a
# pool of threads over every core of the host.
for _var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ[_var] = "1"


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, otherwise the fixed directory ``.jax_cache`` at the
    root of the checkout.  Every program is cached, however fast it
    compiled, so that a second run of a cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    enable_compile_cache()
    from bench import harness

    try:
        result, lines = harness.run_cell(harness.load_cell(args.workload),
                                         args.seed,
                                         args.seconds, bool(args.trace),
                                         t0=T0)
    except harness.NoChip as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 3
    sys.stderr.write("".join(line + "\n" for line in lines))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
