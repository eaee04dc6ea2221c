"""The fused kernels compile for a TPU v5e at real widths, with no chip.

Each test lowers one entry point of the main path for a described
``v5e:2x2`` topology (one of its chips) with the kernels forced compiled,
and asserts that the TPU compiler accepted the program: it holds a
``tpu_custom_call`` and the kernel's named scope shows the levels and
pipeline depth that were asked for, not a clamped fallback.  This is what
interpret mode cannot check: SMEM/VMEM budgets, tiling alignment and
Mosaic lowering.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library, and under pytest-xdist every
worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ata, ata_full
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure to describe it
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: a
    program compiled for a described chip is written to the cache but
    cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    """Compiled text of ``fn`` at ``args``; asserts it runs a kernel."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("n,depth", [(5000, 1), (5000, 2), (10000, None)])
def test_ata_full_auto_compiles(one_chip, n, depth):
    """The README quickstart at the paper's sizes: levels="auto" is 3, and
    the default depth of a compiled kernel is 2."""
    text = _compile(lambda a: ata_full(a, levels="auto", mode="fused",
                                       interpret=False,
                                       pipeline_depth=depth),
                    _f32(one_chip, n, n))
    scope = "fused:ata:l3:strassen:strassen"
    assert scope in text
    assert (scope + ":pd2" in text) == (depth != 1)


def test_aat_levels3_compiles(one_chip):
    text = _compile(lambda a: ata(a, gram_of="rows", levels=3, mode="fused",
                                  interpret=False),
                    _f32(one_chip, 4096, 4096))
    assert "fused:aat:l3:" in text


def test_rank_k_levels3_compiles(one_chip):
    t = 4096 // 256
    text = _compile(
        lambda c, a: ops.rank_k_update(c, a, levels=3, bk=256,
                                       interpret=False, donate=False),
        _f32(one_chip, t * (t + 1) // 2 * 256, 256),
        _f32(one_chip, 4096, 4096))
    assert "fused:rank_k:l3:" in text


def test_matmul_levels3_compiles(one_chip):
    """Levels 3 is the deepest Strassen matmul the fan-in and SMEM table
    clamps keep."""
    text = _compile(lambda a, b: ops.matmul_fused(a, b, levels=3,
                                                  interpret=False),
                    _f32(one_chip, 4096, 4096), _f32(one_chip, 4096, 4096))
    assert "fused:matmul:l3:" in text


def test_symm_gradient_levels3_compiles(one_chip):
    text = _compile(
        jax.grad(lambda a, w: jnp.sum(
            ops.ata_fused(a, levels=3, interpret=False) * w)),
        _f32(one_chip, 4096, 4096), _f32(one_chip, 4096, 4096))
    assert "fused:symm:l3:" in text


def test_ata_dps_levels2_compiles(one_chip):
    text = _compile(lambda a: ata(a, gram="dps", levels=2, mode="fused",
                                  interpret=False),
                    _f32(one_chip, 4096, 4096))
    assert "fused:ata:l2:strassen:dps" in text


def test_ata_fp8_operand_tiles_compile(one_chip):
    text = _compile(lambda a: ata(a, levels="auto", mode="fused",
                                  interpret=False,
                                  operand_dtype=jnp.float8_e4m3fn),
                    _f32(one_chip, 5000, 5000))
    assert "fused:ata:l3:" in text
