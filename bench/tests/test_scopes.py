"""Self-check of the per-scope reduction (``bench/scopes.py``): the wire
decoder against ``ProfileData`` on a recorded trace, hand-made events with
hand-computed numbers, and a small trace of the program's scopes and spans
recorded on a TPU v5e (``data/v5e_scopes.xplane.pb``, written by
``bench/scope_probe.py --toy``) reduced against a brute-force count.

    PYTHONPATH=.:src python3 -m pytest -q bench/tests/test_scopes.py
"""
from pathlib import Path

import numpy as np
import pytest

from bench import scopes, trace

DATA = Path(__file__).resolve().parent / "data"
SMALL = str(DATA / "v5e_small.xplane.pb")
SCOPED = str(DATA / "v5e_scopes.xplane.pb")


def op(tf_op, s, e):
    return scopes.Op("op", s, e, tf_op, 1)


def test_decoder_reads_the_op_metadata():
    devices, host = scopes.read_xplane(SMALL)
    (ops,) = devices.values()
    dots = [o for o in ops if o.tf_op]
    assert {o.tf_op for o in dots} == {"jit(<lambda>)/dot_general:"}
    assert all(o.program_id > 0 and o.name.startswith("%fusion")
               for o in dots)
    assert len({o.program_id for o in ops}) == 1


def test_decoder_keeps_the_times_of_profile_data():
    devices, host = scopes.read_xplane(SMALL)
    t_devices, t_host = trace.read_xplane(SMALL)
    assert {k: [(o.start_ns, o.end_ns) for o in v]
            for k, v in devices.items()} == {
        k: [(s, e) for _, s, e in v] for k, v in t_devices.items()}
    assert sorted(host) == sorted(t_host)


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(f)/while/body/decsvm.round/dot_general:", "decsvm.round"),
    ("jit(f)/decsvm.round/while/body/decsvm.kkt_check/mul:",
     "decsvm.kkt_check"),
    ("jit(f)/while/body/closed_call/jit(_where)/select_n:", "unscoped"),
    ("jit(f)/add;jit(f)/decsvm.rho/mul;jit(f)/decsvm.rho/dot:",
     "decsvm.rho"),
    ("", "unscoped"),
])
def test_scope_of(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def test_scope_seconds_by_leaves():
    host = [("bench:request", 0, 100)]
    devices = {"/device:TPU:0": [
        op("jit(f)/while:", 5, 40),                       # holds two
        op("jit(f)/while/body/decsvm.round/dot:", 5, 15),
        op("jit(f)/while/body/decsvm.kkt_check/dot:", 20, 40),
        op("jit(f)/decsvm.rho/dot:", 50, 60),
        op("", 70, 80),                                    # no metadata
        op("jit(f)/decsvm.bic/dot:", 95, 130),            # clipped at 100
    ]}
    s = scopes.reduce(devices, host)
    assert s.scope_seconds == pytest.approx({
        "decsvm.round": 10e-9, "decsvm.kkt_check": 20e-9,
        "decsvm.rho": 10e-9, "unscoped": 10e-9, "decsvm.bic": 5e-9})
    assert s.leaf_s == pytest.approx(55e-9)
    assert s.share("decsvm.kkt_check") == pytest.approx(20 / 55)
    assert s.share("decsvm.nothing") == 0.0


def test_idle_is_cut_at_every_span_edge():
    # One gap, [10, 90], crosses three program spans; the midpoint rule
    # would give all 80 ns to the span at 50.
    host = [("bench:request", 0, 100),
            ("bench:select_lambda_path", 0, 95),
            ("decsvm:lambda_grid", 5, 30),
            ("np.asarray", 6, 29),                         # not a span
            ("decsvm:path_program", 30, 60),
            ("decsvm:bic_table", 60, 85),
            ("decsvm:bic_table", 70, 75)]                  # nested, inner
    devices = {"/device:TPU:0": [op("a", 0, 10), op("b", 90, 100)]}
    s = scopes.reduce(devices, host)
    assert s.span_idle_seconds == pytest.approx({
        "decsvm:lambda_grid": 20e-9, "decsvm:path_program": 30e-9,
        "decsvm:bic_table": 25e-9, "outside program": 5e-9})
    assert sum(s.span_idle_seconds.values()) == pytest.approx(80e-9)
    busy = trace.reduce({"/device:TPU:0": [("a", 0, 10), ("b", 90, 100)]},
                        host)
    assert busy.gap_seconds == pytest.approx(
        {"select_lambda_path > decsvm:path_program": 80e-9})


def test_two_devices_average_and_no_window_is_an_error():
    host = [("bench:request", 0, 10), ("decsvm:path_program", 0, 10)]
    s = scopes.reduce({"/device:TPU:0": [op("x/decsvm.round/y:", 0, 10)],
                       "/device:TPU:1": [op("x/decsvm.round/y:", 0, 4)]},
                      host)
    assert s.scope_seconds == pytest.approx({"decsvm.round": 7e-9})
    assert s.span_idle_seconds == pytest.approx(
        {"decsvm:path_program": 3e-9})
    with pytest.raises(ValueError):
        scopes.reduce({"/device:TPU:0": [op("a", 0, 1)]}, [])
    with pytest.raises(ValueError):
        scopes.reduce({}, host)


def _brute(devices, host):
    """Scope seconds from leaves found pairwise, and idle seconds by span
    from a mask at nanosecond resolution."""
    marks = [(s, e) for n, s, e in host if n == trace.WINDOW_SPAN]
    w0, w1 = int(min(s for s, _ in marks)), int(max(e for _, e in marks))
    spans = sorted([h for h in host if h[0].startswith("decsvm:")],
                   key=lambda h: h[1] - h[2])             # longest first
    scope_ns, idle_ns = {}, {}
    for ops in devices.values():
        iv = [(o.tf_op, max(o.start_ns, w0), min(o.end_ns, w1))
              for o in ops if o.end_ns > w0 and o.start_ns < w1]
        for k, (t, s, e) in enumerate(iv):
            inner = any(j != k and s <= s2 and e2 <= e and (s2, e2) != (s, e)
                        for j, (_, s2, e2) in enumerate(iv))
            if not inner:
                sc = scopes.scope_of(t)
                scope_ns[sc] = scope_ns.get(sc, 0) + (e - s)
        busy = np.zeros(w1 - w0, bool)
        for _, s, e in iv:
            busy[int(s) - w0:int(e) - w0] = True
        label = np.full(w1 - w0, scopes.OUTSIDE, object)
        for n, s, e in spans:                            # inner ones last
            label[max(int(s), w0) - w0:max(min(int(e), w1) - w0, 0)] = n
        for n in set(label[~busy]):
            idle_ns[n] = idle_ns.get(n, 0) + int(np.sum(label[~busy] == n))
    nd = len(devices)
    return ({k: v / nd * 1e-9 for k, v in scope_ns.items()},
            {k: v / nd * 1e-9 for k, v in idle_ns.items()})


def test_recorded_v5e_trace_of_the_program():
    devices, host = scopes.read_xplane(SCOPED)
    assert list(devices) == ["/device:TPU:0"]
    assert sum(n == trace.WINDOW_SPAN for n, _, _ in host) == 2
    assert {n for n, _, _ in host if n.startswith("decsvm:")} == set(
        scopes.SPANS)
    s = scopes.reduce(devices, host)
    assert set(s.scope_seconds) - {scopes.UNSCOPED} == set(scopes.SCOPES)
    assert 1.0 - s.share(scopes.UNSCOPED) >= 0.95
    scope_s, idle_s = _brute(devices, host)
    assert s.scope_seconds == pytest.approx(scope_s, rel=1e-9, abs=1e-12)
    assert s.span_idle_seconds == pytest.approx(idle_s, rel=1e-9, abs=1e-12)
    # the same window and busy time as bench/trace.py
    t = trace.reduce(*trace.read_xplane(SCOPED))
    assert s.window_s == t.window_s
    assert sum(s.span_idle_seconds.values()) == pytest.approx(
        t.window_s - t.busy_s)
