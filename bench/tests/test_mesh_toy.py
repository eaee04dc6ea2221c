"""The four-chip cell's path on four CPU devices: the program is correct;
the control, the exchange between chips left out, half the rows left out
and an altered answer are not."""
import pytest

from bench.tests.toy import run_cases

CASES = [
    ("program", None, True),
    ("float8_e4m3fn", None, False),
    ("program", "no_exchange", False),
    ("program", "half_batch", False),
    ("program", "altered", False),
]


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    cases = [{"workload": "toy-gram.mesh4", "system": s, "fault": f,
              "seed": 4242, "seconds": 0.3} for s, f, _ in CASES]
    out = run_cases(tmp_path_factory.mktemp("mesh"), cases, devices=4)
    return {(r["case"]["system"], r["case"]["fault"]): r for r in out}


@pytest.mark.parametrize("system,fault,correct", CASES)
def test_mesh_correct_only_when_sound(results, system, fault, correct):
    r = results[(system, fault)]
    assert r["attempted"] > 0
    assert r["correct"] is correct, r["checks"]
