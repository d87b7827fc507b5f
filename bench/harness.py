"""One run of one cell: set-up, the measured window, the check against the
reference, and the metrics.

Everything that belongs to one configuration, traffic mix, entry point,
loop, metric or cell lives in a file of its own, found by its name:

    configs/<config>.json     the deployment (sizes, rules, guarantees)
    traffic/<traffic>.json    the mix: entry point, loop, pool, warm-up
    ops/<op>.py               build / control / check / combine
    loops/<loop>.py           drive
    metrics/<metric>.py       read(run) -> value or None
    checks/<workload>.json    the limit of each number compared

A cell runs on the first ``chips`` devices of the machine, and not at all
where it has fewer (``check_devices``).  Its data is
made on them by ``design.make_pool``: whole on one chip, and sharded along
the node axis over a 1-D mesh of the cell's chips where it has several (the
configuration's m has to divide evenly over them).  An op module that runs
an engine over several chips finds them in ``X.sharding``.  The graph is
the configuration's ``graph`` (``"erdos_renyi"`` with ``graph_p``, or
``"k_regular"`` with ``graph_k``).  A traced run hands a metric reader two
reductions of its trace: ``Run.trace`` (``bench/trace.py``: busy time, op
time by name, idle gaps by the host) and ``Run.program``
(``bench/scopes.py``: device time by the program's ``decsvm.`` scopes, and
idle time by its ``decsvm:`` spans).
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    checks: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str) -> Cell:
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=name, chips=w["chips"],
                config=load_json(ROOT / conf["file"]),
                traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                checks=load_json(BENCH / "checks" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


def check_devices(chips: int, require_chip: bool = True):
    """The cell's devices: the first ``chips`` of the machine, exactly that
    many.  Raises ``NoChip`` where there are fewer, and, unless
    ``require_chip`` is false (the self-tests on CPU devices), off the
    accelerator."""
    import jax

    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}")
    if require_chip:
        from bench import costs
        costs.peaks(devices[0].device_kind)
    return devices[:chips]


def request_order(seed: int, pool: int):
    """Keys of the pool in a seeded order, a fresh permutation per pass."""
    from bench import design

    rng = design.host_rng(seed, 2)
    while True:
        yield from rng.permutation(pool).tolist()


@dataclasses.dataclass
class Run:
    """What a metric reader may read."""
    cell: Cell
    device_kind: str
    setup_s: float
    records: list                      # (key, sent, done, answer, counters,
    #                                     error) of every request sent
    trace: object = None               # trace.Summary of a traced run
    program: object = None             # scopes.Summary of a traced run

    @property
    def done(self):
        return [r for r in self.records if r[5] is None]

    @property
    def window_s(self) -> float:
        """From the first request sent to the last one returned."""
        return self.records[-1][2] - self.records[0][1]

    def latencies(self):
        return [r[2] - r[1] for r in self.done]

    def counters(self, key: str):
        return [r[4][key] for r in self.done if key in r[4]]

    def peaks(self) -> dict:
        from bench import costs
        return costs.peaks(self.device_kind)


def finite(x):
    """A number JSON can carry: non-finite readings become the largest
    float, which fails every limit."""
    return x if math.isfinite(x) else sys.float_info.max


def answer_digest(key, answer: dict) -> str:
    h = hashlib.sha1(repr(key).encode())
    for name in sorted(answer):
        a = np.asarray(answer[name])
        h.update(f"{name}{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def judge(numbers: dict, limits: dict):
    """Each number of the check beside its limit: a number passes where it
    is at most its limit, and a number missing from ``numbers`` fails.
    Requests that failed are a number of their own, with the limit 0.
    Returns (checks, correct, one line per number)."""
    limits = dict(limits, failed_requests=0)
    numbers = dict({"failed_requests": 0}, **numbers)
    checks = {k: {"value": finite(numbers.get(k, math.inf)),
                  "limit": limits[k]} for k in limits}
    ok = {k: c["value"] <= c["limit"] for k, c in checks.items()}
    lines = [f"check {k}: {c['value']!r} (limit {c['limit']!r}) "
             f"{'ok' if ok[k] else 'FAILED'}" for k, c in checks.items()]
    return checks, all(ok.values()), lines


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _traced(drive, log_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        return drive()
    finally:
        jax.profiler.stop_trace()


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t0: float, require_chip: bool = True):
    """Run the cell once.  Returns (result dict, lines of the check)."""
    import jax
    from jax.profiler import TraceAnnotation

    from bench import design, scopes
    from bench import trace as trace_mod

    devices = check_devices(cell.chips, require_chip)
    cfg, traffic = cell.config, cell.traffic
    op = load_module("ops", traffic["op"])
    loop = load_module("loops", traffic["loop"])

    pool, W = design.make_pool(cfg, seed, traffic["pool"], devices)
    request = op.build(cfg, traffic, W)

    def call(key):
        with TraceAnnotation("bench:request"):
            return request(*pool[key])

    for k in range(traffic["warmup"]):
        jax.block_until_ready(call(k % len(pool)))
    setup_s = time.perf_counter() - t0

    order = request_order(seed, len(pool))
    drive = lambda **kw: loop.drive(call, order, **kw)
    summary = program = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            records = _traced(lambda: drive(count=traffic["trace_requests"]),
                              log_dir)
            summary = trace_mod.summarize(log_dir)
            program = scopes.summarize(log_dir)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
    else:
        records = drive(seconds=seconds)
    mem = memory_peak_bytes(devices)

    run = Run(cell=cell, device_kind=devices[0].device_kind,
              setup_s=setup_s, records=records, trace=summary,
              program=program)
    failed = [r for r in records if r[5] is not None]
    for r in failed[:5]:
        print(f"request on data set {r[0]} failed: {r[5]}", file=sys.stderr)
    lat = sorted(run.latencies())
    if lat:
        print(f"latency s: min {lat[0]!r} median {lat[len(lat) // 2]!r} "
              f"max {lat[-1]!r} of {len(lat)}; setup_s {setup_s!r}",
              file=sys.stderr)

    # The check: every answer of the window against the reference run for
    # the rounds that answer reports, after the window has closed; answers
    # that are equal bit for bit are checked once.
    checked, per_answer = {}, []
    for key, _, _, answer, _, _ in run.done:
        digest = answer_digest(key, answer)
        if digest not in checked:
            X, y = pool[key]
            checked[digest] = op.check(cfg, traffic, W, X, y, answer)
        per_answer.append(checked[digest])
    numbers = op.combine(per_answer) if per_answer else {}
    numbers["failed_requests"] = len(failed)
    checks, correct, lines = judge(numbers, cell.checks["limits"])
    correct = correct and bool(run.done)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": len(records),
              "failed": len(failed), "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = checks
    return result, lines

