"""Record a small trace of the program's scopes and spans for the tests.

    python3 bench/scope_probe.py --toy bench/tests/data/v5e_scopes.xplane.pb

It records a few rounds of ``decsvm_fit_tol`` and one
``select_lambda_path`` at a toy size, each under a ``bench:request`` span
and with the harness's profiler options, and writes the ``.xplane.pb`` to
the path given without its ``/host:metadata`` plane (the programs' HLO,
which neither reader reads, and most of the file's bytes).  It needs a
TPU.  A cell's own readings by scope and span are the metrics of its traced
run, which read ``Run.program``.
"""
import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


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
    ap.add_argument("--toy", required=True,
                    help="record the toy trace to this path")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import run                          # bench/run.py, beside this file
    run.enable_compile_cache()
    from bench import harness
    try:
        out = record_toy(args.toy)
    except harness.NoChip as e:
        print(f"scope_probe: {e}; refusing to run", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
