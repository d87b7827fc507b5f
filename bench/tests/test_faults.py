"""Each cell's check against faults planted in the timed path, and against
its control: the harness runs as on the chip (set-up, window, reference,
compare) but skips its look for a TPU, at a size the CPU holds, and
``correct`` has to come out false for every fault and true without one.
The control (the reference one precision lower, in the program's place) is
judged as a run judges its numbers, and has to come out not correct.

    JAX_PLATFORMS=cpu PYTHONPATH=.:src python3 -m pytest -q \
        bench/tests/test_faults.py
"""
import dataclasses
import time

import pytest

from bench import control, harness
from bench.faults import FAULTS

SMALL = {"paper41_p500.path": {"m": 4, "n": 40, "p": 30},
         "epsilon_m10.fit": {"m": 4, "n": 60, "p": 80}}


def small_cell(name):
    cell = harness.load_cell(name)
    return dataclasses.replace(
        cell, config=dict(cell.config, **SMALL[name]),
        traffic=dict(cell.traffic, pool=2, warmup=1))


def run(name, seed):
    result, lines = harness.run_cell(small_cell(name), seed, 0.3, False,
                                     t0=time.perf_counter(),
                                     require_chip=False)
    return result, lines


@pytest.mark.parametrize("name", sorted(SMALL))
def test_sound_run_is_correct(name):
    result, lines = run(name, 2**31 + 11)
    assert result["correct"], lines
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", sorted(SMALL))
def test_fault_is_caught(name, fault):
    with FAULTS[fault](name):
        result, lines = run(name, 2**31 + 12)
    assert not result["correct"], lines


@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_is_caught(name):
    cell = small_cell(name)
    numbers = control.readings(cell, 2**31 + 13, "control",
                               require_chip=False)
    _, correct, lines = harness.judge(numbers, cell.checks["limits"])
    assert not correct, lines
