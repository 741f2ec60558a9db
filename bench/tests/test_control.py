"""The control: the reference in bfloat16, put in the program's place,
must come out not correct; the program must come out correct. At a size a
test run can hold; ``bench/control.py`` reads the same at the cells' own
size on the chip."""

import pytest

import control
import harness

CELLS = ["sift128-hybrid-u8.batch1024", "deep96-hybrid-fs4.online16",
         "deep96-hybrid-fs4.batch1024", "sift128-rpq-u8.batch1024"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_program_passes(cell, tiny, no_disk_cache):
    args = harness.SimpleNamespace(workload=cell, seconds=1.0, study=False)
    out, = control.readings(args, [7], require_chip=False, overrides=tiny)
    _, _, conf, _, _, _ = harness.find_cell(cell)
    limits = {k: v for k, v in conf["limits"].items() if k in out["program"]}
    assert limits
    assert all(out["program"][k] <= v for k, v in limits.items())
    assert any(out["control"][k] > v for k, v in limits.items())


def test_fit_control_fails_and_program_passes(tiny, no_disk_cache):
    """``--fit-keys``: on each key the program's first steps pass the fit
    limits and the bfloat16 control fails one; a planted fault fails one."""
    cell = "sift128-rpq-u8.batch1024"
    args = harness.SimpleNamespace(workload=cell, fit_keys=[1, 2],
                                   fit_faults=["fit_state_unchanged"])
    out = list(control.fit_readings(args, require_chip=False,
                                    overrides=tiny))
    _, _, conf, _, _, _ = harness.find_cell(cell)
    limits = {k: v for k, v in conf["limits"].items() if k.startswith("fit_")}
    assert len(limits) == 3 and len(out) == 4   # two keys, then the fault on each
    for line in out:
        over = [line["program"][k] > v for k, v in limits.items()]
        if line["fault"] is None:
            assert not any(over)
            assert any(line["control"][k] > v for k, v in limits.items())
        else:
            assert any(over)
