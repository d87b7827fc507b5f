"""The metric readers of ``Run.program`` (``bench/metrics/kkt_share.fit.py``
and ``program_idle_ms.path.py``) on the trace of the program's scopes and
spans recorded on a TPU v5e (``data/v5e_scopes.xplane.pb``: one fit and one
tuned path, each under a ``bench:request`` span), against the numbers the
reduction gives, and on runs that read nothing.

    PYTHONPATH=.:src python3 -m pytest -q bench/tests/test_program_metrics.py
"""
from pathlib import Path

import pytest

from bench import harness, scopes

SCOPED = str(Path(__file__).resolve().parent / "data" /
             "v5e_scopes.xplane.pb")


def run_of(program, requests=2):
    records = [(k, float(k), k + 0.5, {}, {}, None) for k in range(requests)]
    return harness.Run(cell=None, device_kind="TPU v5 lite", setup_s=1.0,
                       records=records, program=program)


@pytest.fixture(scope="module")
def program():
    return scopes.reduce(*scopes.read_xplane(SCOPED))


def test_kkt_share_reads_the_scope(program):
    kkt = harness.load_module("metrics", "kkt_share.fit")
    value = kkt.read(run_of(program))
    leaf = sum(program.scope_seconds.values())
    assert value == pytest.approx(
        100.0 * program.scope_seconds["decsvm.kkt_check"] / leaf)
    assert 0.0 < value < 100.0


def test_program_idle_reads_the_spans_per_request(program):
    idle = harness.load_module("metrics", "program_idle_ms.path")
    spans = {k: v for k, v in program.span_idle_seconds.items()
             if k != scopes.OUTSIDE}
    assert set(spans) == set(scopes.SPANS)
    assert idle.read(run_of(program)) == pytest.approx(
        1e3 * sum(spans.values()) / 2)
    assert idle.read(run_of(program, 4)) == pytest.approx(
        1e3 * sum(spans.values()) / 4)


@pytest.mark.parametrize("name", ["kkt_share.fit", "program_idle_ms.path"])
def test_nothing_to_read_is_nothing(name, program):
    read = harness.load_module("metrics", name).read
    assert read(run_of(None)) is None
    bare = scopes.Summary(window_s=1.0, devices=1,
                          scope_seconds={scopes.UNSCOPED: 0.5},
                          span_idle_seconds={scopes.OUTSIDE: 0.5})
    assert read(run_of(bare)) is None
    assert read(run_of(program)) > 0.0
