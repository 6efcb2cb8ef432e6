"""The control at a small size: the reference one precision down (three-pass
bfloat16), put in the program's place, is refused by the cell's limits,
while the reference at the stated precision passes them."""

import pytest

from bench import check, control, spec

BENCH = spec.benchmark()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_control_is_refused(cell, small):
    w = spec.workload(BENCH, cell)
    cfg = spec.load_json("configs", w["config"])
    traffic = spec.load_json("traffic", w["traffic"])
    limits = spec.load_json("limits", cell)
    for seed in (3, 2**33 + 4):
        sound = check.judge(control.control_numbers(
            cfg, traffic, seed, precision="highest"), limits)
        assert check.passed(sound), sound
        low = check.judge(control.control_numbers(cfg, traffic, seed),
                          limits)
        assert not check.passed(low), low
