"""Reduce a cell's traced run by the program's own scopes and spans, or
record a small trace of them for the tests.

    python3 bench/scope_probe.py --workload epsilon_m10.fit --seed 7
    python3 bench/scope_probe.py --toy bench/tests/data/v5e_scopes.xplane.pb

With ``--workload`` it runs the cell as ``run.py --trace 1`` does, through
``harness.run_cell``, and reduces the same trace a second time with
``bench/scopes.py``: the harness keeps no trace after its own reduction,
so for the length of the run ``trace.summarize`` is wrapped to do both.
The last line of stdout is one JSON object: the harness's result, and
under ``program`` the seconds by scope and span with the numbers read from
them (shares of leaf time, the rounds' roofline, idle time under the
program's spans per request).

With ``--toy`` it records a few rounds of ``decsvm_fit_tol`` and one
``select_lambda_path`` at a toy size, each under a ``bench:request`` span
and with the harness's profiler options, and writes the ``.xplane.pb`` to
the path given without its ``/host:metadata`` plane (the programs' HLO,
which neither reader reads, and most of the file's bytes).  Both need a
TPU.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def probe(workload: str, seed: int) -> dict:
    from bench import costs, harness, scopes, trace

    held = {}
    summarize = trace.summarize

    def both(log_dir):
        held["program"] = scopes.summarize(log_dir)
        return summarize(log_dir)

    trace.summarize = both
    try:
        cell = harness.load_cell(workload)
        result, _ = harness.run_cell(cell, seed, 0.0, True, t0=T0)
    finally:
        trace.summarize = summarize
    prog, c = held["program"], cell.config
    done = result["attempted"] - result["failed"]
    per_request = (result["metrics"].get("path_rounds")
                   or result["metrics"]["rounds_per_fit"])
    rounds = done * per_request["value"]
    round_s = prog.scope_seconds.get("decsvm.round", 0.0)
    nbytes = rounds * costs.streaming_bytes_per_round(c["m"], c["n"],
                                                      c["p"] + 1)
    hbm = costs.peaks(result["device"]["kind"])["hbm_bytes_per_s"]
    in_spans = sum(v for k, v in prog.span_idle_seconds.items()
                   if k != scopes.OUTSIDE)
    result["program"] = {
        "scope_seconds": prog.scope_seconds,
        "span_idle_seconds": prog.span_idle_seconds,
        "scoped_share": 100.0 * (1.0 - prog.share(scopes.UNSCOPED)),
        "kkt_share": 100.0 * prog.share("decsvm.kkt_check"),
        "rho_share": 100.0 * prog.share("decsvm.rho"),
        "bic_share": 100.0 * prog.share("decsvm.bic"),
        "round_roofline": (100.0 * nbytes / hbm / round_s if round_s
                           else None),
        "program_idle_ms": 1e3 * in_spans / done if done else None,
        "traced_wall_s": prog.window_s / done if done else None,
        "rounds": rounds,
    }
    return result


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append(n & 0x7F | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def drop_planes(src: str, dst: str, names) -> None:
    """Copy an XSpace without the planes of the given names."""
    from bench import scopes

    out = bytearray()
    with open(src, "rb") as f:
        space = memoryview(f.read())
    for field, val in scopes._fields(space):
        if (field == scopes.SPACE_PLANES
                and scopes._plane(val)[0] in names):
            continue
        if not isinstance(val, memoryview):
            raise ValueError("XSpace holds only length-delimited fields")
        out += _varint(field << 3 | 2) + _varint(len(val)) + val
    with open(dst, "wb") as f:
        f.write(out)


def record_toy(out: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation

    from bench import harness, scopes, trace
    from repro.core import ADMMConfig, decsvm_fit_tol, tuning

    harness.check_devices(1)
    m, n, p = 4, 32, 24
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(m, n, p)), jnp.float32)
    y = jnp.asarray(np.where(rng.normal(size=(m, n)) > 0, 1.0, -1.0),
                    jnp.float32)
    W = jnp.asarray(np.roll(np.eye(m), 1, 1) + np.roll(np.eye(m), -1, 1),
                    jnp.float32)
    cfg = ADMMConfig(lam=0.05, tau=1.0, h=0.5, kernel="epanechnikov",
                     max_iter=8)
    fit = lambda: jax.block_until_ready(decsvm_fit_tol(
        X, y, W, cfg, tol=1e-6, stop_rule="kkt", check_every=4))
    path = lambda: tuning.select_lambda_path(X, y, W, cfg, num=3, tol=1e-6)
    fit(), path()
    log_dir = tempfile.mkdtemp(prefix="scope_probe_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            for request in (fit, path):
                with TraceAnnotation("bench:request"):
                    request()
        finally:
            jax.profiler.stop_trace()
        drop_planes(trace.find_xplane(log_dir), out, {"/host:metadata"})
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    s = scopes.reduce(*scopes.read_xplane(out))
    return {"bytes": Path(out).stat().st_size,
            "scope_seconds": s.scope_seconds,
            "span_idle_seconds": s.span_idle_seconds,
            "scoped_share": 100.0 * (1.0 - s.share(scopes.UNSCOPED))}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--toy", help="record the toy trace to this path")
    args = ap.parse_args()
    if bool(args.workload) == bool(args.toy):
        ap.error("give one of --workload and --toy")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import run                          # bench/run.py, beside this file
    run.enable_compile_cache()
    from bench import harness
    try:
        out = (record_toy(args.toy) if args.toy
               else probe(args.workload, args.seed))
    except harness.NoChip as e:
        print(f"scope_probe: {e}; refusing to run", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
