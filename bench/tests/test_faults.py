"""A run with the timed path broken underneath comes out not correct.

Drives the whole of `run.run_cell` except the look for a chip, at a small
size on the CPU, once for each fault a one-chip solo cell can have."""

import json
import os
import time

import pytest

import control
import run

HERE = os.path.dirname(os.path.abspath(__file__))


def run_small(tmp_path, seed=2**31 + 21):
    with open(os.path.join(HERE, "small.json")) as f:
        config = json.load(f)
    with open(os.path.join(run.BENCH, "traffic", "solo.json")) as f:
        mix = json.load(f)
    cell = {"name": "small.solo", "config": "small", "traffic": "solo",
            "chips": 1}
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    return run.run_cell(cell, config, mix, seed, 0.5, False, device,
                        time.perf_counter(), out_dir=str(tmp_path))


def test_sound_run_is_correct(tmp_path):
    *_, checks, correct = run_small(tmp_path)
    assert correct, checks


@pytest.mark.parametrize("fault", sorted(control.FAULTS))
def test_fault_is_caught(tmp_path, fault):
    with control.FAULTS[fault]():
        _, _, attempted, failed, checks, correct = run_small(tmp_path)
    assert not correct, (fault, checks)
