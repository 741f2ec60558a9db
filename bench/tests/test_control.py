"""The control: the reference in bfloat16, put in the program's place,
must come out not correct; the program must come out correct. At a size a
test run can hold; ``bench/control.py`` reads the same at the cells' own
size on the chip."""

import pytest

import control
import harness

CELLS = ["sift128-hybrid-u8.batch1024", "deep96-hybrid-fs4.online16"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_program_passes(cell, tiny, no_disk_cache):
    args = harness.SimpleNamespace(workload=cell, seconds=1.0, study=False)
    out, = control.readings(args, [7], require_chip=False, overrides=tiny)
    _, _, conf, _, _, _ = harness.find_cell(cell)
    limits = {k: v for k, v in conf["limits"].items() if k in out["program"]}
    assert limits
    assert all(out["program"][k] <= v for k, v in limits.items())
    assert any(out["control"][k] > v for k, v in limits.items())
