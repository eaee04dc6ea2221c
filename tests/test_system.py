"""System-level checks: dry-run artifacts well-formed, HLO cost analyzer
trip-count correctness (conftest multidevice harness), end-to-end mini
train via the launch CLI."""
import json
import glob
import pathlib

import pytest

HERE = pathlib.Path(__file__).parent
REPO = HERE.parent
ART = REPO / "artifacts" / "dryrun"


def test_dryrun_artifacts_wellformed():
    files = glob.glob(str(ART / "*.json"))
    if not files:
        pytest.skip("no dry-run artifacts yet (run repro.launch.dryrun)")
    for f in files:
        with open(f) as fh:
            a = json.load(fh)
        if a.get("status") != "ok":
            continue
        assert a["memory"]["temp_size_in_bytes"] >= 0
        if a.get("kind") != "gram":
            assert a["cost_corrected"]["flops"] > 0, a["cell"]
            assert a["cost_corrected"]["unknown_trip_loops"] == 0, a["cell"]
        assert "wire_bytes_total" in (a.get("collectives_corrected")
                                      or a["collectives"])


def test_dryrun_covers_assigned_grid():
    """32 runnable cells (40-cell grid minus 8 mandated long_500k skips)
    x both meshes must be present and ok once the sweep has run."""
    files = glob.glob(str(ART / "*__pod2x16x16.json"))
    if len(files) < 10:
        pytest.skip("multi-pod sweep incomplete")
    from repro.configs.registry import all_cells
    for arch, shape in all_cells():
        for mesh in ("pod16x16", "pod2x16x16"):
            p = ART / f"{arch}__{shape}__{mesh}.json"
            assert p.exists(), f"missing dry-run cell {p.name}"
            with open(p) as fh:
                assert json.load(fh)["status"] == "ok", p.name


@pytest.mark.multidevice(8)
def test_hlo_cost_trip_count(multidevice_count):
    """The trip-count-aware HLO analyzer against a known scan program on
    an 8-device host platform (conftest multidevice harness)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.roofline.hlo_cost import analyze_hlo
    from repro.launch.mesh import make_mesh

    L, B, D = 48, 64, 128

    def f(xs, w):
        def body(c, _):
            c = jnp.tanh(c @ w)
            return c, ()
        c, _ = jax.lax.scan(body, xs, None, length=L)
        return jnp.sum(c)

    mesh = make_mesh((8,), ("data",))
    sh = NamedSharding(mesh, P("data", None))
    c = jax.jit(f, in_shardings=(sh, None),
                out_shardings=NamedSharding(mesh, P())).lower(
        jax.ShapeDtypeStruct((B, D), jnp.float32),
        jax.ShapeDtypeStruct((D, D), jnp.float32)).compile()
    r = analyze_hlo(c.as_text())

    dot_flops = L * 2 * (B // 8) * D * D           # per-device
    assert 0.95 * dot_flops < r["flops"] < 1.3 * dot_flops, (
        r["flops"], dot_flops)
    xla_cost = c.cost_analysis()
    if isinstance(xla_cost, (list, tuple)):   # jax 0.4.x returns [dict]
        xla_cost = xla_cost[0]
    assert xla_cost["flops"] < dot_flops / 10, "xla undercounts (expected)"
    # bytes: per iteration ~ w (D*D*4) + 3x carry; x L
    per_iter = D * D * 4 + 3 * (B // 8) * D * 4
    assert r["bytes"] > 0.8 * L * per_iter * 0.5, (r["bytes"],
                                                   L * per_iter)
    assert r["unknown_trip_loops"] == 0
    # collective: the final psum of a scalar
    assert r["collectives"]["by_kind"].get("all-reduce", {}).get("count",
                                                                 0) >= 1


def test_train_cli_end_to_end(tmp_path):
    from repro.launch.train import main
    hist = main(["--arch", "qwen2.5-3b", "--reduced", "--steps", "4",
                 "--batch", "2", "--seq", "16",
                 "--workdir", str(tmp_path)])
    assert len(hist) == 4


def test_serve_cli_end_to_end():
    from repro.launch.serve import main
    finished = main(["--arch", "qwen2.5-3b", "--requests", "2",
                     "--slots", "2", "--max-seq", "64", "--max-new", "4"])
    assert len(finished) == 2


def test_chip_smoke_refuses_cpu():
    """chip_smoke.py measures the chip or nothing: with no TPU it exits
    non-zero before any phase and prints no result line."""
    import os
    import subprocess
    import sys
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         env=env, cwd=REPO, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert "no TPU" in out.stdout
    assert '"ok"' not in out.stdout
