"""Schema for the repo-root ``BENCH_<name>.json`` benchmark artifacts.

Every JSON-emitting bench (``benchmarks/bench_megakernel.py``,
``bench_lambda_path.py``, ``bench_fit_serving.py``)
writes the same core shape; CI and ``benchmarks/run.py --bench <name>``
validate the artifact against this module so a bench refactor cannot
silently drop the fields the ROADMAP acceptance gates read.

Core shape::

    {
      "bench": "<name>",                     # matches BENCH_<name>.json
      "config": {"backend": "cpu", ...},     # backend is mandatory
      "end_to_end_s":   {"variant": 1.23, ...},   # compile + first run
      "steady_state_s": {"variant": 0.12, ...},   # cached-program reruns
      "speedup_*": 4.2,                      # at least one, finite, > 0
      "criteria": {"gate_name": true, ...}   # pass/fail acceptance gates
    }

``validate`` returns a list of problem strings (empty = valid) rather
than raising, so callers choose their own failure mode.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import List, Optional

REQUIRED_KEYS = ("bench", "config", "end_to_end_s", "steady_state_s",
                 "criteria")


def _is_finite_pos(v) -> bool:
    return (isinstance(v, (int, float)) and not isinstance(v, bool)
            and math.isfinite(v) and v > 0)


def validate(doc, name: Optional[str] = None) -> List[str]:
    """Validate one parsed BENCH artifact; return problems (empty = ok).

    ``name``: when given, ``doc["bench"]`` must equal it (the artifact
    filename convention ``BENCH_<name>.json``).
    """
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"artifact must be a JSON object, got {type(doc).__name__}"]
    for key in REQUIRED_KEYS:
        if key not in doc:
            problems.append(f"missing required key {key!r}")
    if problems:
        return problems

    if not isinstance(doc["bench"], str) or not doc["bench"]:
        problems.append("'bench' must be a non-empty string")
    elif name is not None and doc["bench"] != name:
        problems.append(f"'bench' is {doc['bench']!r}, expected {name!r} "
                        "(must match the BENCH_<name>.json filename)")

    config = doc["config"]
    if not isinstance(config, dict):
        problems.append("'config' must be an object")
    elif not isinstance(config.get("backend"), str):
        problems.append("'config.backend' must be a string "
                        "(which stack produced these numbers?)")

    for key in ("end_to_end_s", "steady_state_s"):
        timings = doc[key]
        if not isinstance(timings, dict) or not timings:
            problems.append(f"{key!r} must be a non-empty object of "
                            "variant -> seconds")
            continue
        # one nesting level is allowed for per-split breakdowns, e.g.
        # steady_state_s["mesh_by_split"]["4x2"]; leaves must be seconds
        for variant, secs in timings.items():
            leaves = (list(secs.items()) if isinstance(secs, dict)
                      else [("", secs)])
            if not leaves:
                problems.append(f"{key}[{variant!r}] is an empty breakdown")
            for sub, v in leaves:
                where = f"{key}[{variant!r}]" + (f"[{sub!r}]" if sub else "")
                if not _is_finite_pos(v):
                    problems.append(f"{where} must be a finite positive "
                                    f"number, got {v!r}")

    speedups = {k: v for k, v in doc.items() if k.startswith("speedup_")}
    if not speedups:
        problems.append("no 'speedup_*' key — every bench must report at "
                        "least one headline ratio")
    for k, v in speedups.items():
        if not _is_finite_pos(v):
            problems.append(f"{k!r} must be a finite positive number, "
                            f"got {v!r}")

    criteria = doc["criteria"]
    if not isinstance(criteria, dict) or not criteria:
        problems.append("'criteria' must be a non-empty object of "
                        "acceptance gates")
    else:
        for gate, ok in criteria.items():
            if not isinstance(ok, bool):
                problems.append(f"criteria[{gate!r}] must be a bool pass/"
                                f"fail gate, got {ok!r}")

    problems.extend(_check_speedup_provenance(doc, speedups))
    return problems


def _timing_leaves(doc) -> List[float]:
    """Every leaf timing in end_to_end_s / steady_state_s (one nesting
    level of per-split breakdowns included)."""
    leaves: List[float] = []
    for key in ("end_to_end_s", "steady_state_s"):
        timings = doc.get(key)
        if not isinstance(timings, dict):
            continue
        for secs in timings.values():
            vals = secs.values() if isinstance(secs, dict) else (secs,)
            leaves.extend(v for v in vals if _is_finite_pos(v))
    return leaves


def _check_speedup_provenance(doc, speedups, rel_tol: float = 1e-3
                              ) -> List[str]:
    """Every headline ``speedup_*`` must be *derivable* from the artifact:
    equal (within ``rel_tol``) to a ratio of two recorded timing leaves.
    A speedup no pair of timings explains is either hand-edited or
    computed from measurements the bench then dropped — both invalidate
    the artifact as the ROADMAP's evidence trail."""
    leaves = _timing_leaves(doc)
    problems: List[str] = []
    for key, s in speedups.items():
        if not _is_finite_pos(s) or not leaves:
            continue  # already reported above
        ok = any(abs(a / b - s) <= rel_tol * s
                 for a in leaves for b in leaves if a is not b)
        if not ok:
            problems.append(
                f"{key!r} = {s} matches no ratio of recorded timings "
                f"(rel tol {rel_tol}) — speedups must be derivable from "
                "end_to_end_s / steady_state_s leaves")
    return problems


def validate_file(path: Path) -> List[str]:
    """Load ``BENCH_<name>.json`` and validate it, inferring the expected
    bench name from the filename."""
    path = Path(path)
    stem = path.stem
    name = stem[len("BENCH_"):] if stem.startswith("BENCH_") else None
    try:
        doc = json.loads(path.read_text())
    except (OSError, ValueError) as e:
        return [f"cannot read/parse {path}: {e}"]
    return validate(doc, name=name)
