"""The reader of ``bench/metrics/halo_share.p2p.py`` on the program's
exchange counter (``repro.core.trace.exchange``): what it reads once
neighbour sums were built, and nothing on a program without the counter.

    PYTHONPATH=.:src python3 -m pytest -q bench/tests/test_halo_share.py
"""
import sys
import types

import pytest

from bench import harness


def run_of(program, requests=2):
    records = [(k, float(k), k + 0.5, {}, {}, None) for k in range(requests)]
    return harness.Run(cell=None, device_kind="TPU v5 lite", setup_s=1.0,
                       records=records, program=program)


def test_halo_share_reads_the_exchange_counter(monkeypatch):
    """halo_share.p2p reads the program's exchange counter: nothing before
    an exchange is built, 2 rows of 256 per offset as 0.78%."""
    from repro.core import trace
    read = harness.load_module("metrics", "halo_share.p2p").read
    counter = trace.ExchangeCounter()
    monkeypatch.setattr(trace, "exchange", counter)
    assert read(run_of(None)) is None
    counter.record(2, 256)
    counter.record(2, 256)
    assert read(run_of(None)) == pytest.approx(100.0 * 4 / 512)


def test_halo_share_is_nothing_without_the_counter(monkeypatch):
    """On a program whose trace module has no exchange counter, as
    compiles.fit on one without the compile counter, the reader gives
    nothing and does not raise."""
    read = harness.load_module("metrics", "halo_share.p2p").read
    monkeypatch.setitem(sys.modules, "repro.core.trace",
                        types.ModuleType("repro.core.trace"))
    assert read(run_of(None)) is None
