"""The control, the reference computed one precision step down, must come
out as not correct; sound solves of the program must come out correct.
Small size, on the CPU; the chip-size readings come from control.py."""

import json
import os

import check
import control

CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "small.json")


def test_control_fails_and_the_program_passes():
    with open(CONFIG) as f:
        config = json.load(f)
    read = dict(control.readings(config, 2**31 + 9))
    ok, _ = check.judge(read["sound"], config["limits"], failed=0)
    assert ok, read["sound"]
    for kind in ("control", "fault:adam_step_unchanged", "fault:half_batch",
                 "fault:answer_altered", "fault:merge_worst_rows"):
        ok, checks = check.judge(read[kind], config["limits"], failed=0)
        assert not ok, (kind, checks)
    ok, checks = check.judge(read["reference"], config["limits"], failed=0)
    assert ok, checks
