"""``BENCHMARK.json`` and the files it names: every cell and metric
resolves by name, the file keeps to the benchmark's contract, a cell
added as files and entries runs, and a run refuses without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench.cell import load_module, reader_of, resolve
from bench.tests.toy import REPO, TOY_CELLS, run_cases

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_to_its_files(workload):
    cell = resolve(REPO, workload)
    assert cell.driver.is_file()
    driver = load_module(cell.driver, f"check_{cell.driver.stem}")
    assert hasattr(driver, "Cell")
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "a cell reports at least one per-layer metric"
    for m, path in cell.per_layer:
        assert m["moves"] in names
        assert callable(load_module(path, f"check_{m['name']}").read)
    assert set(cell.limits) >= {"rel_fro", "worst_rows_rel_fro"}


def test_configs_are_files_under_paths():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    for c in SPEC["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])


def test_benchmark_json_keeps_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][1].startswith(SPEC["paths"][0] + "/")
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    for group in (SPEC["configs"], SPEC["workloads"], metrics):
        names = [x["name"] for x in group]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert set(m["workloads"]) <= set(CELLS)
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 2)
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_per_layer_metric_has_a_reader(metric):
    path = reader_of(REPO / "bench", metric)
    assert callable(load_module(path, f"check_reader_{metric}").read)


def test_reader_is_its_own_file_before_its_family(tmp_path):
    layers = tmp_path / "layers"
    layers.mkdir()
    (layers / "idle_share.py").write_text("")
    assert reader_of(tmp_path, "idle_share.new") == layers / "idle_share.py"
    (layers / "idle_share.new.py").write_text("")
    assert reader_of(tmp_path, "idle_share.new") == \
        layers / "idle_share.new.py"
    with pytest.raises(FileNotFoundError):
        reader_of(tmp_path, "roofline.new")


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    proc = _run_py(REPO)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench")
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    proc = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


ONE_CHIP = [c[0] for c in TOY_CELLS if c[3] == 1]


@pytest.fixture(scope="module")
def toy_runs(tmp_path_factory):
    cases = [{"workload": w, "seed": 12345, "seconds": 0.3}
             for w in ONE_CHIP]
    out = run_cases(tmp_path_factory.mktemp("toy"), cases)
    return {r["case"]["workload"]: r for r in out}


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_cell_added_as_files_runs(toy_runs, workload):
    r = toy_runs[workload]
    assert r["correct"] is True and r["attempted"] > 0


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_setup_phases_add_up_to_setup_s(toy_runs, workload):
    r = toy_runs[workload]
    phases = r["setup"]
    assert {"start_s", "devices_s", "cell_s"} <= set(phases)
    assert all(v >= 0 for v in phases.values())
    top = phases["start_s"] + phases["devices_s"] + phases["cell_s"]
    assert top == pytest.approx(r["metrics"]["setup_s"]["value"], abs=1e-6)
    inner = sum(v for k, v in phases.items()
                if k not in ("start_s", "devices_s", "cell_s"))
    assert 0 < inner <= phases["cell_s"]


def test_compile_cache_stays_in_the_checkout(tmp_path, monkeypatch):
    from bench import run
    # set (not deleted) so that the fixture restores what the run sets
    for name in ("JAX_COMPILATION_CACHE_DIR", "REPRO_AUTOTUNE_CACHE",
                 "TPU_LOG_DIR"):
        monkeypatch.setenv(name, "/elsewhere")
    state = run._prepare_env(tmp_path)
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == \
        str(tmp_path / ".jax_cache")
    assert state == tmp_path / ".bench_state"
    assert os.environ["REPRO_AUTOTUNE_CACHE"].startswith(str(state))
