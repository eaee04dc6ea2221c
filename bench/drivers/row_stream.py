"""Normal equations over a stream of row chunks, closed loop: each chunk
goes from host memory into a jitted ``stream.stack_update`` that donates
the packed state, and the next is sent once the update has completed.

The chunks are drawn in turn from a host pool that set-up makes on the
device from the seed and copies to the host once.

End to end: ``rows_per_s``, the rows of every update completed in the
window over the window.  Checked: the finalized state after every update
of the window, against the sum, over the pool, of each chunk's
``jnp.dot`` Gram at HIGHEST precision times the times it was sent, and
the state's row count against the rows sent.  Control: the reference
with the chunk rounded to ``run.system``'s type, accumulated into a
dense fp32 state, in the program's place.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from bench import compare, work
from bench.cell import Window


class Cell:
    def __init__(self, run):
        from repro.gram import stream
        cfg, mix = run.cell.config, run.cell.traffic
        self.n = n = int(cfg["n"])
        self.rows = rows = int(mix["chunk_rows"])
        size = int(mix["pool"])
        dtype = jnp.dtype(cfg["dtype"])
        self.dev = run.devices[0]
        t0 = time.perf_counter()
        pool = jax.jit(lambda k: jax.random.normal(k, (size, rows, n), dtype),
                       out_shardings=SingleDeviceSharding(self.dev))(
            run.key())
        self.pool = np.asarray(pool)
        del pool
        t1 = time.perf_counter()
        if run.system == "program":
            levels = cfg["levels"]
            self.init = lambda: stream.stack_init(n)
            self.update = jax.jit(
                lambda st, ch: stream.stack_update(st, ch, levels=levels),
                donate_argnums=0)
            self.finalize = lambda st: (stream.stack_finalize(st, n),
                                        int(st.rows))
        else:
            low = jnp.dtype(run.system)

            def update(st, ch):
                return st[0] + compare.gram(ch, operand_dtype=low), \
                    st[1] + ch.shape[0]
            self.init = lambda: (jnp.zeros((n, n), jnp.float32),
                                 jnp.zeros((), jnp.int32))
            self.update = jax.jit(update, donate_argnums=0)
            self.finalize = lambda st: (st[0], int(st[1]))
        jax.block_until_ready(self.update(self.init(), self.pool[0]))
        self.state = jax.block_until_ready(self.init())
        self.phases = {"pool_s": t1 - t0,
                       "compile_warm_s": time.perf_counter() - t1}
        self.sent = np.zeros(size, np.int64)
        self.got = None

    def window(self, seconds):
        size = len(self.pool)
        chunks = 0
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench:update"):
                self.state = jax.block_until_ready(
                    self.update(self.state, self.pool[chunks % size]))
            self.sent[chunks % size] += 1
            chunks += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        rows = chunks * self.rows
        packed_state = self.n * (self.n + 1) // 2 * 4
        return Window(
            elapsed, {"rows_per_s": rows / elapsed}, chunks, 0,
            flop=chunks * work.gram_flop(self.rows, self.n),
            bytes=chunks * work.gram_bytes(self.rows, self.n,
                                           self.pool.dtype.itemsize, 0,
                                           state_bytes=packed_state),
            calls=chunks, extra={"rows": rows})

    def release(self):
        self.got = self.finalize(self.state)
        self.state = self.update = None

    def check(self):
        got, rows = self.got
        want = jnp.zeros((self.n, self.n), jnp.float32, device=self.dev)
        for i, count in enumerate(self.sent):
            if count:
                ch = jax.device_put(self.pool[i], self.dev)
                want = want + float(count) * compare.gram(ch)
        return {**compare.errors(got, want),
                "rows_miscounted": abs(rows - int(self.sent.sum()) * self.rows)}
