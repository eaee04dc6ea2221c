#!/usr/bin/env python3
"""Bring-up smoke of the fused Gram path on a TPU.

    python chip_smoke.py             # one chip: library, rows + gradient,
                                     # streaming, the service
    python chip_smoke.py --chips 4   # distributed_gram on a four-chip mesh

Every phase runs through the public entry points at the paper's sizes
(§6.2: n = 5000 and 10000), checks its result against a plain reference
and asserts that its compiled program holds a ``tpu_custom_call``: the
Pallas kernel ran compiled, not interpreted.  The service phase also
fails on any retry, failure or degraded result, so a request served
down the engine's ladder by the XLA reference recursion cannot pass as a
kernel result.  Times printed here are bring-up, not a benchmark.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed.  The script exits non-zero, and
prints no such line, when JAX finds no TPU, when a phase fails, or when
it is run outside a checkout of the repository.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# Relative Frobenius error every 256 x 256 tile of a result must meet
# against its reference.  fp32 operands reach the MXU as one bf16 pass
# (unit roundoff 2^-8), and each Strassen level loses about a bit in the
# recombination, so a levels-3 result is good to about 2^-8 * 2^3 = 3.1e-2;
# the bound allows twice that.  A missing, stale or misplaced tile is off
# by order 1 in that tile, which a bound on the whole matrix can miss.
TILE = 256
REL_FRO_BOUND = 2.0 ** -7 * 2 ** 3

# Sizes: the paper's §6.2 grams, a 4096 row gram and gradient, 65,536
# streamed rows in four 256 MiB chunks, and a 1 GiB A on four chips.
LIBRARY_N = (5000, 10000)
ROWS_N = 4096
STREAM_ROWS, STREAM_N, STREAM_CHUNKS = 16384, 4096, 4
DIST_SHAPE = (32768, 8192)

_KERNEL_SCOPE = re.compile(r"fused:(\w+):l(\d+):(\w+):(\w+)(?::pd(\d+))?")
_cache_events = {"hits": 0, "misses": 0}


def _on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        _cache_events["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _cache_events["misses"] += 1


def log(msg):
    print(msg, flush=True)


def kernels_of(compiled, label):
    """The fused kernels a compiled program runs, as
    ``kind:l<levels>:pd<depth>``; raises unless it holds a
    ``tpu_custom_call``."""
    text = compiled.as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError(f"{label}: no tpu_custom_call in the compiled "
                             "program (the kernel did not run compiled)")
    return sorted({f"{k}:l{lv}:pd{pd or 1}" for k, lv, _, _, pd
                   in _KERNEL_SCOPE.findall(text)})


def rel_fro(got, want):
    """Relative Frobenius error ``||got - want|| / ||want||`` of the whole
    result and of its worst TILE x TILE tile, in float64 on the host for
    numpy references and in fp32 on the device otherwise.  A tile where
    the reference is zero counts as exact only if the result is too."""
    xp = np if isinstance(want, np.ndarray) else jnp
    if xp is np:
        got = np.asarray(got, np.float64)
    pad = [(0, -d % TILE) for d in want.shape]

    def tile_sq(x):
        x = xp.pad(x, pad)
        r, c = x.shape
        return (x * x).reshape(r // TILE, TILE, c // TILE, TILE).sum((1, 3))

    num, den = tile_sq(got - want), tile_sq(want)
    ratio = xp.where(den > 0, num / xp.where(den > 0, den, 1),
                     xp.where(num > 0, xp.inf, 0))
    return (float(xp.sqrt(num.sum() / den.sum())),
            float(xp.sqrt(ratio.max())))


def check(label, err):
    """Fail unless the worst tile meets REL_FRO_BOUND; returns the errors
    as text."""
    whole, worst = err
    if not worst <= REL_FRO_BOUND:
        raise AssertionError(f"{label}: relative Frobenius error {worst:.3e} "
                             f"in its worst tile > {REL_FRO_BOUND:.3e}")
    return f"rel_fro {whole:.3e} (worst tile {worst:.3e})"


def compile_and_time(fn, *args):
    """(compiled, compile seconds, result, seconds of one timed call after
    a first call)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return compiled, compile_s, out, time.perf_counter() - t0


def timing(compile_s, call_s):
    return (f"compile {compile_s:.2f}s, one call {call_s * 1e3:.1f}ms "
            "(bring-up, not a benchmark)")


def normal(seed, shape, sharding=None):
    return jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32),
                   out_shardings=sharding)(jax.random.key(seed))


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------

def phase_library(seed):
    """ata_full(a, levels="auto") with mode "auto" at the paper's sizes."""
    from repro.core import ata_full
    for n in LIBRARY_N:
        a = normal(seed + n, (n, n))
        compiled, cs, c, ts = compile_and_time(
            lambda x: ata_full(x, levels="auto"), a)
        kern = kernels_of(compiled, f"ata_full n={n}")
        if n == LIBRARY_N[0]:
            a64 = np.asarray(a, np.float64)
            want, ref = a64.T @ a64, "float64 numpy"
        else:
            want = jnp.dot(a.T, a, precision=jax.lax.Precision.HIGHEST)
            ref = "jnp.dot HIGHEST"
        err = check(f"ata_full n={n}", rel_fro(c, want))
        log(f"  ata_full n={n} fp32: kernels {kern}; {err} vs {ref}; "
            f"{timing(cs, ts)}")


def phase_rows_and_grad(seed):
    """ata(a, gram_of="rows") and jax.grad through ops.ata_fused (the
    symm kernel) at n = 4096."""
    from repro.core import ata
    from repro.kernels import ops
    n = ROWS_N
    a = normal(seed, (n, n))
    a64 = np.asarray(a, np.float64)
    compiled, cs, c, ts = compile_and_time(
        lambda x: ata(x, gram_of="rows", levels="auto"), a)
    kern = kernels_of(compiled, "ata rows")
    err = check("ata rows", rel_fro(c, np.tril(a64 @ a64.T)))
    log(f"  ata(gram_of='rows') n={n}: kernels {kern}; {err} vs float64 "
        f"numpy; {timing(cs, ts)}")

    w = normal(seed + 1, (n, n))

    def loss(x):
        return jnp.sum(ops.ata_fused(x, levels=3) * w)

    compiled, cs, g, ts = compile_and_time(jax.grad(loss), a)
    kern = kernels_of(compiled, "ata_fused grad")
    if not any(k.startswith("symm:") for k in kern):
        raise AssertionError(f"ata_fused grad: no symm kernel in {kern}")
    s = jnp.tril(w)
    want = jnp.dot(a, s + s.T, precision=jax.lax.Precision.HIGHEST)
    err = check("ata_fused grad", rel_fro(g, want))
    # what one bf16 pass costs without Strassen: XLA's default precision
    base, _ = rel_fro(jnp.dot(a, s + s.T), want)
    log(f"  grad(ops.ata_fused) n={n}: kernels {kern}; {err} vs "
        f"A (S + S^t) HIGHEST (XLA default precision: {base:.3e}); "
        f"{timing(cs, ts)}")


def phase_stream(seed):
    """Normal equations over 65,536 rows: stack_init + four stack_update
    chunks of 16384 x 4096 (the rank_k kernel) + stack_finalize."""
    from repro.gram import stream
    rows, n, n_chunks = STREAM_ROWS, STREAM_N, STREAM_CHUNKS
    state = stream.stack_init(n)
    update = jax.jit(lambda st, ch: stream.stack_update(st, ch,
                                                        levels="auto"),
                     donate_argnums=0)
    want = jnp.zeros((n, n), jnp.float32)
    compile_s = call_s = 0.0
    kern = None
    for i in range(n_chunks):
        chunk = normal(seed + i, (rows, n))
        if kern is None:
            t0 = time.perf_counter()
            compiled = update.lower(state, chunk).compile()
            compile_s = time.perf_counter() - t0
            kern = kernels_of(compiled, "stack_update")
        t0 = time.perf_counter()
        state = jax.block_until_ready(compiled(state, chunk))
        call_s = time.perf_counter() - t0
        want = want + jnp.dot(chunk.T, chunk,
                              precision=jax.lax.Precision.HIGHEST)
    if int(state.rows) != rows * n_chunks:
        raise AssertionError(f"stream counted {int(state.rows)} rows")
    c = stream.stack_finalize(state, n)
    err = check("stream", rel_fro(c, want))
    log(f"  stack_update x{n_chunks} ({rows}x{n} fp32 chunks): kernels "
        f"{kern}; {err} vs sum of chunk^t chunk HIGHEST; "
        f"{timing(compile_s, call_s)} (last chunk)")


# mixed shapes from 256 to 4096, both grams
SERVICE_REQUESTS = [((256, 256), "cols"), ((512, 384), "cols"),
                    ((1000, 700), "rows"), ((2048, 1024), "cols"),
                    ((4096, 4096), "cols"), ((3000, 2500), "rows"),
                    ((256, 2048), "rows"), ((1500, 256), "cols")]


def phase_service(seed):
    """GramEngine(slots=4) drains mixed requests; every one must be served
    ok by the local kernel at rung 0, none retried or degraded."""
    from repro.gram import GramEngine
    rng = np.random.default_rng(seed)
    eng = GramEngine(slots=4)
    reqs = []
    for shape, gram_of in SERVICE_REQUESTS:
        a = rng.standard_normal(shape).astype(np.float32)
        reqs.append((a, gram_of, eng.submit(a, gram_of=gram_of)))
    t0 = time.perf_counter()
    eng.run_to_completion()
    wall = time.perf_counter() - t0
    stats = eng.stats()
    if stats["retries"] or stats["failed"] or stats["degraded_served"]:
        raise AssertionError(
            f"service: retries={stats['retries']} failed={stats['failed']} "
            f"degraded={stats['degraded_served']}")
    worst = 0.0
    for a, gram_of, fut in reqs:
        c, r = fut.result(), fut.request
        if (r.status, r.served_by, r.degraded) != ("ok", "local", False):
            raise AssertionError(
                f"service: {a.shape} {gram_of}: status={r.status} "
                f"served_by={r.served_by} degraded={r.degraded} "
                f"error={r.error}")
        a64 = a.astype(np.float64)
        want = a64 @ a64.T if gram_of == "rows" else a64.T @ a64
        err = rel_fro(c, want)
        check(f"service {a.shape} {gram_of}", err)
        worst = max(worst, err[1])
    # the bucket executables the engine compiled (rung 0 only: the
    # degraded rungs would have shown up as retries above)
    kern = sorted({k for exe in eng._executables.values()
                   for k in kernels_of(exe, "service bucket")})
    log(f"  GramEngine(slots=4): {len(reqs)} requests ok at rung 0, "
        f"{len(stats['buckets'])} buckets, {stats['compile_count']} "
        f"compiles, kernels {kern}; worst tile rel_fro {worst:.3e} vs float64 "
        f"numpy; drain {wall:.2f}s incl. compiles (bring-up, not a "
        "benchmark)")


# ---------------------------------------------------------------------------
# Four-chip phase
# ---------------------------------------------------------------------------

def phase_distributed(seed):
    """distributed_gram over make_gram_mesh meshes of the four chips,
    each scheme against the single-chip ata_full of the same A."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import ata_full
    from repro.core.distributed import (default_gram_axes, distributed_gram,
                                        scheme_fallback_chain)
    from repro.launch.mesh import make_gram_mesh
    m, n = DIST_SHAPE
    devs = jax.devices()
    a0 = normal(seed, (m, n), jax.sharding.SingleDeviceSharding(devs[0]))
    compiled, cs, want, ts = compile_and_time(
        lambda x: ata_full(x, levels="auto"), a0)
    log(f"  single-chip ata_full {m}x{n}: kernels "
        f"{kernels_of(compiled, 'single-chip ata_full')}; {timing(cs, ts)}")
    want = jax.device_put(want, devs[0])
    meshes = {"allreduce": dict(ring=1), "reducescatter": dict(ring=1),
              "ring": dict(ring=4), "bfs25d": dict(rep=2),
              "auto": dict(ring=2)}
    for scheme, shape in meshes.items():
        mesh = make_gram_mesh(**shape)
        axes = default_gram_axes(mesh)
        spec = (P(axes["row_axis"], None)
                if scheme in ("allreduce", "reducescatter")
                else P(axes["row_axis"], axes["col_axis"]))
        a = normal(seed, (m, n), NamedSharding(mesh, spec))
        compiled, cs, c, ts = compile_and_time(
            lambda x: distributed_gram(x, mesh, scheme=scheme, **axes), a)
        kern = kernels_of(compiled, f"distributed_gram {scheme}")
        for label, arr in (("input", a), ("result", c)):
            on = {s.device for s in arr.addressable_shards}
            if len(on) != 4:
                raise AssertionError(f"{scheme}: {label} is on {len(on)} "
                                     "devices, not 4")
        err = check(f"distributed_gram {scheme}",
                    rel_fro(jax.device_put(c, devs[0]), want))
        if scheme == "auto":
            scheme += "->" + scheme_fallback_chain(m, n, mesh, **axes)[0]
        log(f"  distributed_gram scheme={scheme} mesh "
            f"{dict(mesh.shape)}: kernels {kern}; input and result on 4 "
            f"devices; {err} vs single-chip ata_full; {timing(cs, ts)}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the distributed_gram phase on a "
                         "four-chip mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})")
        return 2
    if len(jax.devices()) < args.chips:
        log(f"chip_smoke: {args.chips} chips asked, "
            f"{len(jax.devices())} found")
        return 2

    from repro.compile_cache import use_persistent_cache
    cache_dir = use_persistent_cache()
    jax.monitoring.register_event_listener(_on_event)
    log(f"device: {dev.device_kind} x{len(jax.devices())}; jax "
        f"{jax.__version__}; compile cache {cache_dir}")

    phases = ([("distributed", phase_distributed)] if args.chips == 4 else
              [("library", phase_library), ("rows_grad", phase_rows_and_grad),
               ("stream", phase_stream), ("service", phase_service)])
    failed = []
    for name, fn in phases:
        log(f"[{name}]")
        t0 = time.perf_counter()
        try:
            fn(args.seed)
            status = "ok"
        except Exception as e:  # noqa: BLE001 — report every phase
            traceback.print_exc()
            failed.append(name)
            status = f"FAILED {type(e).__name__}: {e}"
        peak = dev.memory_stats().get("peak_bytes_in_use", 0)
        log(f"[{name}] {status} in {time.perf_counter() - t0:.1f}s; "
            f"peak_bytes_in_use {peak}")
    log(f"compile cache: {_cache_events['hits']} hits, "
        f"{_cache_events['misses']} misses")
    if failed:
        log(f"chip_smoke: phases failed: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
