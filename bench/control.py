"""Readings that set the limits of a cell's check, each on every data set of
the cell's pool for a seed, held to the reference by the cell's own
``check``:

- sound: the program's answers, on every seed given;
- control: the reference computed at "bf16x3", the nearest precision below
  the fp32 at HIGHEST that the configurations state, in the program's
  place, on the first ``--control`` seeds;
- each fault of ``faults.py`` planted in the program, on the first
  ``--faults`` seeds.

The pool is made as a run of the cell makes it (``design.make_pool`` on
the first ``chips`` devices: whole on one, sharded along the node axis over
several, m dividing evenly over them), with the configuration's graph
(``graph``: ``"erdos_renyi"`` with ``graph_p``, or ``"k_regular"`` with
``graph_k``).

    python3 bench/control.py --workload paper41_p500.path \
        --seeds 1 2 3 4 5 6 7 8 9 10 11 12 --control 3 --faults 3

Each reading is judged as a run of the benchmark judges its numbers
(``harness.judge`` against ``checks/<workload>.json``).  Prints one JSON
line per reading with its verdict and, last, the largest sound reading and
the smallest reading of the control and of each fault, for each number.
Exits 1 where a sound reading comes out not correct, or the control or a
fault comes out correct: then the limits do not separate them.  A fault
whose numbers equal the sound numbers of the same seed changed no answer
(a looser stop where no fit stops before its round cap): it is reported as
``no_effect``, a fault this cell cannot have.  It needs the TPU; the
benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def readings(cell, seed: int, kind: str, require_chip: bool = True) -> dict:
    """The cell's numbers for one seed; kind is "sound", "control" or the
    name of a fault that is planted already.  Off the accelerator only with
    ``require_chip`` false (the self-tests)."""
    from bench import design, harness

    op = harness.load_module("ops", cell.traffic["op"])
    pool, W = design.make_pool(cell.config, seed, cell.traffic["pool"],
                               harness.check_devices(cell.chips,
                                                     require_chip))
    if kind == "control":
        answer = lambda X, y: op.control(cell.config, cell.traffic, W, X, y)
    else:
        request = op.build(cell.config, cell.traffic, W)
        answer = lambda X, y: request(X, y)[0]
    return op.combine([op.check(cell.config, cell.traffic, W, X, y,
                                answer(X, y)) for X, y in pool])


def summary(rows) -> dict:
    """Per number: the largest sound reading, the smallest control
    reading, and the smallest reading of each fault."""
    out = {}
    for r in rows:
        for k, v in r["numbers"].items():
            s = out.setdefault(k, {})
            if r["kind"] == "sound":
                s["sound_max"] = max(s.get("sound_max", v), v)
            elif r["kind"] == "control":
                s["control_min"] = min(s.get("control_min", v), v)
            elif not r["no_effect"]:
                f = s.setdefault("faults", {})
                f[r["kind"]] = min(f.get(r["kind"], v), v)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="seeds (the first ones) that also run the control")
    ap.add_argument("--faults", type=int, default=0,
                    help="seeds (the first ones) that run each fault")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from bench.faults import FAULTS
    from bench.run import enable_compile_cache

    enable_compile_cache()
    cell = harness.load_cell(args.workload)
    try:
        harness.check_devices(cell.chips)
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    rows, sound = [], {}

    def record(seed, kind):
        t0 = time.perf_counter()
        numbers = readings(cell, seed, kind)
        _, correct, lines = harness.judge(numbers, cell.checks["limits"])
        if kind == "sound":
            sound[seed] = numbers
        row = {"seed": seed, "kind": kind, "numbers": numbers,
               "correct": correct, "as_expected": correct == (kind == "sound"),
               "no_effect": kind not in ("sound", "control")
               and numbers == sound.get(seed),
               "seconds": time.perf_counter() - t0}
        row["as_expected"] = row["as_expected"] or row["no_effect"]
        rows.append(row)
        print(json.dumps(row), flush=True)
        if not row["as_expected"]:
            print(f"control: {kind} on seed {seed} came out "
                  f"{'correct' if correct else 'not correct'}: "
                  + "; ".join(lines), file=sys.stderr)

    for seed in args.seeds:
        record(seed, "sound")
    for seed in args.seeds[:args.control]:
        record(seed, "control")
    for name, fault in FAULTS.items():
        if args.faults:
            with fault(args.workload):
                for seed in args.seeds[:args.faults]:
                    record(seed, name)
    bad = [(r["kind"], r["seed"]) for r in rows if not r["as_expected"]]
    no_effect = sorted({r["kind"] for r in rows if r["no_effect"]})
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "summary": summary(rows), "no_effect": no_effect,
                      "not_as_expected": bad}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
