"""ATA hot-path trajectory: fused schedule vs reference recursion vs jnp.dot.

Emits ``BENCH_ata.json`` (artifacts/bench/) so the perf trajectory of the
single hottest path in the repo — C = tril(A^t A) — is tracked from this
PR onward.  Per treatment we record:

* wall-clock (this host; the fused Pallas kernel runs *interpreted* off-TPU,
  so its absolute time is an emulation artifact — tracked for trend only),
* HBM-materialized intermediate bytes.  Reference/dot: measured with
  ``roofline.hlo_census.hbm_intermediate_census`` over the compiled HLO
  (what XLA actually materializes: operand sums, Strassen M_i products,
  pad/concatenate copies).  Fused: the analytic kernel model
  (``strassen_fused.ata_traffic_model``) — on hardware the kernel writes
  only the packed output, with no HBM temporaries beyond an optional
  pad copy; the raw census of the interpret-mode *emulation* is reported
  alongside for transparency.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from repro.core import ata
from repro.core.cost_model import ir_leaf_count
from repro.core.leaf_ir import compile_program
from repro.gram.verify import default_rtol, freivalds_gram
from repro.kernels.strassen_fused import (aat_traffic_model,
                                          ata_traffic_model,
                                          rank_k_traffic_model)
from repro.kernels import ops
from repro.roofline.hlo_census import hbm_intermediate_census
from .common import timeit_detail, write_json

LEVELS = 2

# Treatments whose hot loop is the generic Pallas kernel: off-TPU these
# run in interpret mode, so their wall clocks are emulation artifacts —
# stamped interpret=True and EXCLUDED from compiled_wall_rows and every
# acceptance key (ISSUE 10).  dot/reference treatments compile natively
# on every backend.
_PALLAS_TREATMENTS = frozenset((
    "fused", "fused_fp8", "aat_fused",
    "rank_k_fused", "rank_k_delta"))


def _is_interpret(name: str) -> bool:
    return (name in _PALLAS_TREATMENTS
            and jax.default_backend() != "tpu")


def _rank_k_zero_stack(n, block):
    t = -(-n // block)
    return jnp.zeros((t * (t + 1) // 2 * block, block), jnp.float32)


def run(quick: bool = False):
    n = 256 if quick else 512
    block = 64 if quick else 128
    leaf = block // 2          # forces the reference recursion to unroll
    a = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.float32)

    stack0 = _rank_k_zero_stack(n, block)

    def rank_k_fused(x):
        # ONE accumulating kernel: the state seeds the VMEM accumulator
        from repro.kernels.strassen_fused import fused_rank_k_update
        return fused_rank_k_update(stack0, x, levels=LEVELS, bk=block)

    def rank_k_delta_baseline(x):
        # status quo (PR 2-4 streamed update): compute the delta stack,
        # then add it into the state — two HBM round trips of the stack
        delta = ops.ata_fused_packed(x, levels=LEVELS, bk=block, bn=block,
                                     out_dtype=jnp.float32)
        return stack0 + delta

    treatments = {
        "dot": lambda x: jnp.tril(
            jnp.dot(x.T, x, preferred_element_type=jnp.float32)),
        "reference": lambda x: ata(x, levels=LEVELS, leaf=leaf,
                                   mode="reference"),
        "fused": lambda x: ops.ata_fused_packed(x, levels=LEVELS, bk=block,
                                                bn=block),
        # fp8 operand tiles, fp32 accumulation — halves(+) the DMA read
        # term; parity is gated by the Freivalds probe below, not here
        "fused_fp8": lambda x: ops.ata_fused_packed(
            x, levels=LEVELS, bk=block, bn=block,
            operand_dtype="float8_e4m3fn"),
        # the two new leaf-IR programs, tracked from day one:
        # row gram (aat) — fused vs reference recursion vs jnp.dot
        "aat_dot": lambda x: jnp.tril(
            jnp.dot(x, x.T, preferred_element_type=jnp.float32)),
        "aat_reference": lambda x: ata(x, gram_of="rows", levels=LEVELS,
                                       leaf=leaf, mode="reference"),
        "aat_fused": lambda x: ops.aat_fused_packed(x, levels=LEVELS,
                                                    bm=block, bk=block),
        # accumulating rank-k update — the fused single-kernel C += A^tA
        # vs the status-quo streamed update (delta stack + add) vs dot
        "rank_k_dot": lambda x: jnp.tril(
            jnp.dot(x.T, x, preferred_element_type=jnp.float32)),
        "rank_k_delta": rank_k_delta_baseline,
        "rank_k_fused": rank_k_fused,
    }

    backend = jax.default_backend()
    rows = []
    for name, fn in treatments.items():
        # one compilation per treatment serves both the timing and the
        # census (interpret-mode Pallas lowering is the expensive step)
        compiled = jax.jit(fn).lower(a).compile()
        detail = timeit_detail(compiled, a,
                               iters=5 if quick else 7)
        wall = detail["wall_s"]
        census = hbm_intermediate_census(compiled.as_text())
        row = {
            "treatment": name,
            "n": n,
            "levels": LEVELS,
            "block": block,
            "wall_s": wall,
            "reps": detail["reps"],
            "warmup": detail["warmup"],
            "backend": backend,
            "interpret": _is_interpret(name),
            "census_total_bytes": census["total_bytes"],
            "census_by_opcode": census["by_opcode"],
        }
        if name in ("fused", "fused_fp8", "aat_fused", "rank_k_fused"):
            if name.startswith("fused"):
                in_b = 1 if name == "fused_fp8" else 4
                model = ata_traffic_model(n, n, levels=LEVELS, bk=block,
                                          bn=block, in_bytes=in_b)
            elif name == "aat_fused":
                model = aat_traffic_model(n, n, levels=LEVELS, bm=block,
                                          bk=block)
            else:
                model = rank_k_traffic_model(n, n, levels=LEVELS, bk=block,
                                             bn=block)
            row["hbm_intermediate_bytes"] = model["intermediate_bytes"]
            row["hbm_write_bytes"] = model["write_bytes"]
            row["hbm_read_bytes"] = model["read_bytes"]
            row["model_flops"] = model["flops"]
            row["model_grid_steps"] = model["grid_steps"]
            row["census_is_interpret_emulation"] = row["interpret"]
        else:
            row["hbm_intermediate_bytes"] = census["total_bytes"]
        rows.append(row)
        tag = "emul" if row["interpret"] else backend
        print(f"[ata] {name:10s} wall {wall*1e3:8.2f} ms ({tag})  "
              f"intermediates {row['hbm_intermediate_bytes']/1e6:8.3f} MB")

    by = {r["treatment"]: r for r in rows}
    ref_b = by["reference"]["hbm_intermediate_bytes"]
    fus_b = by["fused"]["hbm_intermediate_bytes"]
    # Tile-aligned shapes give the fused kernel literally zero HBM
    # intermediates, so a ratio would be a meaningless magnitude; record
    # the raw byte counts (the trackable trajectory) and a ratio only
    # when the denominator is real.
    ratio = (ref_b / fus_b) if fus_b else None
    print(f"[ata] HBM intermediates: reference {ref_b/1e6:.3f} MB vs "
          f"fused {fus_b/1e6:.3f} MB "
          f"({'ratio %.1fx' % ratio if ratio else 'fused has none'}; "
          f"acceptance: reference >= 2x fused)")
    # the new leaf-IR programs' trajectories: aat (row gram) and the
    # accumulating rank-k update, fused vs their baselines
    aat_ref_b = by["aat_reference"]["hbm_intermediate_bytes"]
    aat_fus_b = by["aat_fused"]["hbm_intermediate_bytes"]
    rk_model = rank_k_traffic_model(n, n, levels=LEVELS, bk=block, bn=block)
    rk_base = rk_model["baseline"]

    # -- the algebra axis: per-(variant, gram) leaf counts + parity ------
    # The mult-count deliverable of the gram-algebra registry: at equal
    # levels the dps recursion G(l) = 2G(l-1) + 3t^(l-1) does fewer leaf
    # products than the paper's 4G(l-1) + 2t^(l-1), with fused parity.
    want = np.tril(np.asarray(a, np.float64).T @ np.asarray(a, np.float64))
    scale = max(np.abs(want).max(), 1.0)
    variant_rows = []
    # (winograd, dps) is excluded: its levels=2 operand fan-in exceeds
    # MAX_OPERAND_TERMS, so the executor clamps the depth and the row's
    # closed-form counts would describe a program the kernel did not run
    for variant, gram in (("strassen", "strassen"), ("strassen", "dps"),
                          ("winograd", "strassen")):
        fn = lambda x: ops.ata_fused(x, levels=LEVELS, variant=variant,
                                     gram=gram, bk=block, bn=block)
        compiled = jax.jit(fn).lower(a).compile()
        detail = timeit_detail(compiled, a)
        wall = detail["wall_s"]
        err = float(np.abs(np.asarray(compiled(a), np.float64)
                           - want).max() / scale)
        prog = compile_program("ata", LEVELS, variant, gram=gram)
        row = {
            "treatment": f"ata_{variant}_{gram}",
            "variant": variant,
            "gram": gram,
            "n": n,
            "levels": LEVELS,
            "leaf_count": ir_leaf_count("ata", LEVELS, variant, gram=gram),
            "mult_count_at_block": prog.mult_count(block, block),
            "wall_s": wall,
            "reps": detail["reps"],
            "warmup": detail["warmup"],
            "backend": backend,
            "interpret": backend != "tpu",    # all variant rows are Pallas
            "parity_max_rel_err": err,
            "parity_ok": err < 1e-5,
        }
        variant_rows.append(row)
        print(f"[ata] {row['treatment']:22s} leaves {row['leaf_count']:4d} "
              f"wall {wall*1e3:8.2f} ms   err {err:.2e}")
    vby = {(r["variant"], r["gram"]): r for r in variant_rows}
    dps_below = (vby[("strassen", "dps")]["leaf_count"]
                 < vby[("strassen", "strassen")]["leaf_count"])
    print(f"[ata] dps leaf count below strassen-gram at levels={LEVELS}: "
          f"{dps_below}")

    # fp8 operand serve parity: the quantized Gram must still satisfy the
    # Freivalds identity at the precision-scaled tolerance — this is the
    # end-to-end check that quantize-after-pad + fp32 accumulation did
    # not silently corrupt the output.
    fp8_c = np.asarray(
        ops.ata_fused(a, levels=LEVELS, bk=block, bn=block,
                      operand_dtype="float8_e4m3fn"))
    fp8_ok, fp8_err = freivalds_gram(
        np.asarray(a), fp8_c, probes=4, full=False,
        rtol=default_rtol("float8_e4m3fn"))
    print(f"[ata] fp8 freivalds at n={n}: ok={fp8_ok} "
          f"rel_err={fp8_err:.3e} (rtol "
          f"{default_rtol('float8_e4m3fn'):.2e})")

    # compiled (non-interpret) wall clocks only — the rows a perf trend
    # may legitimately be built on.  Off-TPU this keeps dot/reference and
    # drops every emulated Pallas row.
    compiled_wall_rows = [
        {k: r[k] for k in ("treatment", "n", "levels", "block", "wall_s",
                           "reps", "warmup", "backend")}
        for r in rows + variant_rows if not r["interpret"]]

    payload = {
        "rows": rows,
        "reference_intermediate_bytes": ref_b,
        "fused_intermediate_bytes": fus_b,
        "intermediate_ratio_ref_over_fused": ratio,
        "acceptance_ref_ge_2x_fused": ref_b >= 2 * fus_b,
        "aat_reference_intermediate_bytes": aat_ref_b,
        "aat_fused_intermediate_bytes": aat_fus_b,
        "aat_acceptance_ref_ge_2x_fused": aat_ref_b >= 2 * aat_fus_b,
        "rank_k_modeled_total_bytes": (
            rk_model["read_bytes"] + rk_model["write_bytes"]
            + rk_model["intermediate_bytes"]),
        "rank_k_baseline_total_bytes": (
            rk_base["read_bytes"] + rk_base["write_bytes"]
            + rk_base["intermediate_bytes"]),
        "variant_rows": variant_rows,
        "acceptance_dps_leaf_count_below_strassen": dps_below,
        "acceptance_variant_parity": all(r["parity_ok"]
                                         for r in variant_rows),
        "backend": backend,
        "compiled_wall_rows": compiled_wall_rows,
        "fp8_freivalds_rel_err": fp8_err,
        "fp8_freivalds_rtol": default_rtol("float8_e4m3fn"),
        "acceptance_fp8_freivalds": bool(fp8_ok),
    }
    path = write_json("BENCH_ata.json", payload)
    print(f"[ata] wrote {path}")
    # separate trend artifact: compiled walls only, one small file a CI
    # run can diff/plot across commits without parsing the full payload
    trend = write_json("BENCH_ata_compiled_wall.json", {
        "backend": backend,
        "rows": compiled_wall_rows,
    })
    print(f"[ata] wrote {trend}")
    return payload


if __name__ == "__main__":
    run()
