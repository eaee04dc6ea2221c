"""Distributed Shampoo's statistics step, closed loop: one caller sends
one step after another, each ``repro.optim.shampoo.update_stats`` of
every gradient block of the configuration (its L and R, one jitted call
that donates the packed statistics), each ending in
``block_until_ready``.  The fp32 gradient blocks are made on the device
from the seed in set-up and reused every step, as the loop reuses its A.

End to end: ``gram_s``, the window's time over the Gram updates
completed in it (two a block a step).  Checked, after N steps from zero
state (the warm-up step counts): each block's L and R against the
closed form (1 - beta2^N) G G^t and (1 - beta2^N) G^t G (beta2 and 1 -
beta2 as the program holds them, in fp32), the block's
``compare.gram`` at HIGHEST precision, whole (``rel_fro``) and by its
worst block of 256 rows, the worst block of the set; and
``steps_miscounted``, the largest gap between N and the steps each
statistic's trace reads back, tr(L) / tr(G G^t) = 1 - beta2^N, which a
lost or doubled step moves by about 1 %.  Control: the same EMA on dense
fp32 state, each Gram the reference of the block rounded to
``run.system``'s type, in the program's place.
"""
from __future__ import annotations

import importlib
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from bench import compare, work
from bench.cell import Window

SIDES = (("l", "rows"), ("r", "cols"))


def _packed_bytes(n: int) -> int:
    """fp32 bytes of a packed n x n lower triangle."""
    return n * (n + 1) // 2 * 4


class Cell:
    def __init__(self, run):
        cfg = run.cell.config
        shampoo = importlib.import_module("repro.optim.shampoo")
        for name in ("init_stats", "update_stats"):
            getattr(shampoo, name)    # fails here, before set-up
        from repro.gram import stream
        # the closures below hold locals, not ``self``: a Cell that holds
        # no reference cycle frees its 7.4 GB when its run ends, before
        # the next run of one process (bench/calibrate.py) allocates
        self.shapes = shapes = [tuple(b) for b in cfg["blocks"]]
        self.beta2 = beta2 = float(cfg["beta2"])
        dev = run.devices[0]
        levels = cfg["levels"]
        t0 = time.perf_counter()

        def make(key):
            return [jax.random.normal(jax.random.fold_in(key, i), s,
                                      jnp.dtype(cfg["dtype"]))
                    for i, s in enumerate(shapes)]
        self.grads = grads = jax.block_until_ready(jax.jit(
            make, out_shardings=SingleDeviceSharding(dev))(run.key()))
        t1 = time.perf_counter()
        if run.system == "program":
            # looked up at every call, so that a fault planted in the
            # module reaches the timed path
            init = lambda: shampoo.init_stats(shapes)  # noqa: E731
            self.update = lambda st: shampoo.update_stats(
                st, grads, beta2, levels=levels)
            finalize = jax.jit(lambda st, n: stream.stack_finalize(st, n),
                               static_argnums=1)
            self.dense = lambda st, side, i: finalize(
                st[side][i], shapes[i][0 if side == "l" else 1])
        else:
            low = jnp.dtype(run.system)

            def step(st, grads, beta, alpha):
                return {side: [beta * s + alpha * compare.gram(
                                   g, gram_of, operand_dtype=low)
                               for s, g in zip(st[side], grads)]
                        for side, gram_of in SIDES}
            jstep = jax.jit(step, donate_argnums=0)

            def init():
                return {side: [jnp.zeros((s[k],) * 2, jnp.float32,
                                         device=dev) for s in shapes]
                        for k, (side, _) in enumerate(SIDES)}
            self.update = lambda st: jstep(st, grads, beta2, 1.0 - beta2)
            self.dense = lambda st, side, i: st[side][i]
        # one warm-up step, among the N checked: fresh statistics are
        # placed as a step's result is, so it compiles the one executable
        self.state = jax.block_until_ready(self.update(init()))
        self.steps = 1
        self.phases = {"gradients_s": t1 - t0,
                       "compile_warm_s": time.perf_counter() - t1}
        self.got = None

    def window(self, seconds):
        steps = 0
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench:step"):
                self.state = jax.block_until_ready(self.update(self.state))
            steps += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        self.steps += steps
        grams = 2 * len(self.shapes) * steps
        l_flop = sum(work.gram_flop(c, r) for r, c in self.shapes)
        r_flop = sum(work.gram_flop(r, c) for r, c in self.shapes)
        l_bytes = sum(work.gram_bytes(c, r, 4, 0,
                                      state_bytes=_packed_bytes(r))
                      for r, c in self.shapes)
        r_bytes = sum(work.gram_bytes(r, c, 4, 0,
                                      state_bytes=_packed_bytes(c))
                      for r, c in self.shapes)
        return Window(
            elapsed, {"gram_s": elapsed / grams}, grams, 0,
            flop=steps * (l_flop + r_flop), bytes=steps * (l_bytes + r_bytes),
            calls=steps,
            extra={"steps": steps, "grams": grams, "l_flop": steps * l_flop,
                   "l_bytes": steps * l_bytes})

    def release(self):
        self.got, self.state, self.update = self.state, None, None

    def check(self):
        # beta2 and 1 - beta2 as the program holds them, in fp32: after n
        # steps from zero a statistic is alpha (1 - beta^n) / (1 - beta)
        # times its block's Gram
        beta = float(np.float32(self.beta2))
        alpha = float(np.float32(1.0 - self.beta2))
        n = self.steps
        scale = alpha * (1.0 - beta ** n) / (1.0 - beta)
        out: dict = {}
        miscounted = 0.0
        for i, g in enumerate(self.grads):
            for side, gram_of in SIDES:
                got = self.dense(self.got, side, i)
                unit = compare.gram(g, gram_of)
                out = compare.worst(out, compare.errors(got, scale * unit))
                # the steps this statistic holds, read from its trace
                left = 1.0 - (float(jnp.trace(got)) / float(jnp.trace(unit))
                              * (1.0 - beta) / alpha)
                read = (round(math.log(left) / math.log(beta)) if left > 0
                        else math.inf)
                miscounted = max(miscounted, abs(read - n))
                del got, unit
        return {**out, "steps_miscounted": miscounted}
