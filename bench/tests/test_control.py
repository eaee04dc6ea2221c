"""The comparison that decides ``correct`` has teeth: at toy sizes on the
CPU, under the real cells' limits, the control (the fp8 operand path in
the program's place) and every fault a cell can have come out not
correct, while the program itself comes out correct."""
import pytest

from bench.tests.toy import run_cases

SEED = 2 ** 31 + 977          # larger than 32 signed bits hold
CASES = [
    # (workload, system, fault, correct)
    ("toy-gram.loop", "program", None, True),
    ("toy-gram.loop", "float8_e4m3fn", None, False),
    ("toy-gram.loop", "program", "half_batch", False),
    ("toy-gram.loop", "program", "altered", False),
    ("toy-stream.stream", "program", None, True),
    ("toy-stream.stream", "float8_e4m3fn", None, False),
    ("toy-stream.stream", "program", "state_unchanged", False),
    ("toy-stream.stream", "program", "half_batch", False),
    ("toy-stream.stream", "program", "altered", False),
]


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    cases = [{"workload": w, "system": s, "fault": f, "seed": SEED,
              "seconds": 0.3} for w, s, f, _ in CASES]
    out = run_cases(tmp_path_factory.mktemp("control"), cases)
    return {(r["case"]["workload"], r["case"]["system"],
             r["case"]["fault"]): r for r in out}


@pytest.mark.parametrize("workload,system,fault,correct", CASES)
def test_correct_only_when_sound(results, workload, system, fault, correct):
    r = results[(workload, system, fault)]
    assert r["attempted"] > 0
    assert r["correct"] is correct, r["checks"]
