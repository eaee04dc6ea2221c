"""Suite-wide fixtures.

* ``_isolated_autotune_cache`` — point the gram autotune cache at a
  per-session tmp file so tests neither read a developer's tuned winners
  under ``artifacts/autotune/`` nor write into the repo.

* ``@pytest.mark.multidevice(n)`` — run the marked test in a CHILD pytest
  process with ``XLA_FLAGS=--xla_force_host_platform_device_count=n``.
  The main pytest process must keep the default 1-device CPU platform
  (XLA_FLAGS is consumed at first jax init and must not be set globally),
  so multi-device tests re-execute their own node id in a subprocess: the
  parent replaces the test body with the subprocess launch, and inside
  the child (marked by ``REPRO_MULTIDEVICE_CHILD``) the body runs
  normally against the forced n-device platform.  Write the test as an
  ordinary pytest function — asserts, parametrize and fixtures all work;
  just keep per-test work small, each marked test pays one interpreter
  start.
"""
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

_CHILD_ENV = "REPRO_MULTIDEVICE_CHILD"


@pytest.fixture(autouse=True)
def _isolated_autotune_cache(tmp_path_factory, monkeypatch):
    path = tmp_path_factory.getbasetemp() / "gram_autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))


@pytest.fixture(autouse=True)
def _reset_obs():
    """Each test starts with a fresh (disabled) tracer; a test that
    enabled tracing cannot leak events into the next one."""
    yield
    from repro.obs import trace
    trace.set_tracer(None)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multidevice(n=8, timeout=600): re-run this test in a child pytest "
        "with XLA_FLAGS=--xla_force_host_platform_device_count=n (the main "
        "process keeps the default 1-device platform)")


def _multidevice_runner(nodeid: str, n: int, timeout: float):
    def run(**_fixtures):
        env = dict(os.environ)
        env[_CHILD_ENV] = str(n)
        env["JAX_PLATFORMS"] = "cpu"       # forced host devices, by design
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count={n}"
                            ).strip()
        env["PYTHONPATH"] = str(REPO / "src") + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        out = subprocess.run(
            [sys.executable, "-m", "pytest", "-x", "-q", "--no-header",
             "-p", "no:cacheprovider", nodeid],
            env=env, cwd=REPO, capture_output=True, text=True,
            timeout=timeout)
        if out.returncode != 0:
            pytest.fail(
                f"multidevice({n}) child failed for {nodeid}\n"
                f"--- stdout ---\n{out.stdout}\n--- stderr ---\n{out.stderr}",
                pytrace=False)
    return run


def pytest_collection_modifyitems(config, items):
    if os.environ.get(_CHILD_ENV):
        return                      # child: run the real test bodies
    for item in items:
        mark = item.get_closest_marker("multidevice")
        if mark is None:
            continue
        n = mark.args[0] if mark.args else mark.kwargs.get("n", 8)
        timeout = mark.kwargs.get("timeout", 600)
        item.obj = _multidevice_runner(item.nodeid, int(n), timeout)


@pytest.fixture
def multidevice_count(request):
    """Device count the surrounding ``multidevice`` mark asked for (child
    side); asserts the forced platform actually materialized."""
    mark = request.node.get_closest_marker("multidevice")
    n = int(mark.args[0] if mark and mark.args
            else (mark.kwargs.get("n", 8) if mark else 1))
    if os.environ.get(_CHILD_ENV):
        import jax
        assert len(jax.devices()) >= n, \
            f"expected >= {n} devices, got {jax.devices()}"
    return n
