"""The Shampoo statistics cell's comparison has teeth: at toy size on the
CPU, under the real cell's limits, the control (the fp8 operand path in
the program's place), a dropped step, an ignored beta and an altered
result come out not correct, while the program comes out correct; and
the cell, added as files, passes the harness's checks of a toy cell."""
import pytest

from bench.tests import test_harness as harness
from bench.tests.toy_shampoo import WORKLOAD, run_cases

SEED = 2 ** 31 + 977          # larger than 32 signed bits hold
CASES = [
    # (system, fault, correct)
    ("program", None, True),
    ("float8_e4m3fn", None, False),
    ("program", "step_dropped", False),
    ("program", "beta_ignored", False),
    ("program", "altered", False),
]


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    cases = [{"workload": WORKLOAD, "system": s, "fault": f, "seed": SEED,
              "seconds": 0.3} for s, f, _ in CASES]
    out = run_cases(tmp_path_factory.mktemp("shampoo_control"), cases)
    return {(r["case"]["system"], r["case"]["fault"]): r for r in out}


@pytest.mark.parametrize("system,fault,correct", CASES)
def test_correct_only_when_sound(results, system, fault, correct):
    r = results[(system, fault)]
    assert r["attempted"] > 0
    assert r["correct"] is correct, r["checks"]


def test_cell_added_as_files_runs(results):
    harness.test_cell_added_as_files_runs(
        {WORKLOAD: results[("program", None)]}, WORKLOAD)


def test_setup_phases_add_up_to_setup_s(results):
    harness.test_setup_phases_add_up_to_setup_s(
        {WORKLOAD: results[("program", None)]}, WORKLOAD)
