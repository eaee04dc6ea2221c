"""Closed loop of dense Grams: one caller sends one (m, n) A back to back,
each call ending in ``block_until_ready``.

On one chip the call is ``repro.core.ata_full(a, levels=...)``.  On
several it is ``repro.core.distributed.distributed_gram`` (its default
levels) over the ``make_gram_mesh`` mesh the configuration names, with A
sharded over the mesh's rows and columns.  A is made on the device from the seed.

End to end: ``gram_s``, the window's time over the Grams completed in it.
Checked: a sample of the window's results, drawn from the seed (and the
last one), against ``jnp.dot`` at HIGHEST precision of the same A.
Controls: "float8_e4m3fn" or "bfloat16", the program's own operand-dtype
path on one chip, the reference with its operand rounded to that type on
several.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from bench import compare, trace_reduce, work
from bench.cell import Window

CHECKED = 2     # results kept from the window besides the last


class Cell:
    def __init__(self, run):
        import repro.core as core
        cfg = run.cell.config
        self.m, self.n = int(cfg["m"]), int(cfg["n"])
        self.dtype = jnp.dtype(cfg["dtype"])
        levels = cfg["levels"]
        control = None if run.system == "program" else run.system
        if len(run.devices) == 1:
            self.sharding = SingleDeviceSharding(run.devices[0])

            def fn(a):
                return core.ata_full(a, levels=levels,
                                     operand_dtype=control)
        else:
            from repro.core.distributed import (default_gram_axes,
                                                distributed_gram)
            from repro.launch.mesh import make_gram_mesh
            dist = cfg["distributed"]
            mesh = make_gram_mesh(devices=run.devices, **dist["mesh"])
            axes = default_gram_axes(mesh)
            self.sharding = NamedSharding(
                mesh, P(axes["row_axis"], axes["col_axis"]))

            def fn(a):
                if control is not None:
                    return compare.gram(a, operand_dtype=control)
                return distributed_gram(a, mesh, scheme=dist["scheme"],
                                        **axes)
        shape = (self.m, self.n)
        t0 = time.perf_counter()
        self.a = jax.block_until_ready(jax.jit(
            lambda k: jax.random.normal(k, shape, self.dtype),
            out_shardings=self.sharding)(run.key()))
        t1 = time.perf_counter()
        self.call = jax.jit(fn).lower(self.a).compile()
        t2 = time.perf_counter()
        self.collectives = trace_reduce.collective_ops(self.call.as_text())
        jax.block_until_ready(self.call(self.a))
        self.phases = {"operand_s": t1 - t0, "compile_s": t2 - t1,
                       "warm_s": time.perf_counter() - t2}
        self.rng = np.random.default_rng(run.seed)
        self.kept: dict = {}
        self.dev0 = run.devices[0]

    def window(self, seconds):
        keep = set(self.rng.choice(16, size=CHECKED, replace=False).tolist())
        calls, out = 0, None
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench:call"):
                out = jax.block_until_ready(self.call(self.a))
            if calls in keep:
                self.kept[calls] = out
            calls += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        self.kept[calls - 1] = out
        flop = calls * work.gram_flop(self.m, self.n)
        nbytes = calls * work.gram_bytes(
            self.m, self.n, self.dtype.itemsize, self.n * self.n)
        return Window(elapsed, {"gram_s": elapsed / calls}, calls, 0,
                      flop=flop, bytes=nbytes, calls=calls,
                      collectives=frozenset(self.collectives),
                      extra={"checked_calls": sorted(self.kept)})

    def release(self):
        self.call = None

    def check(self):
        want = compare.gram(jax.device_put(self.a, self.dev0))
        out: dict = {}
        for c in self.kept.values():
            out = compare.worst(out, compare.errors(
                jax.device_put(c, self.dev0), want))
        return out
