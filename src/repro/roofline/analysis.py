"""Three-term roofline from dry-run artifacts (TPU v5e peaks from
``core.cost_model.PEAKS``).

Per (arch x shape x mesh) cell, from the compiled dry-run:
  compute term    = HLO_FLOPs_per_device / peak_FLOP/s
  memory term     = HLO_bytes_per_device / HBM_bw
  collective term = wire_bytes_per_device / ICI_link_bw

cost_analysis() of a partitioned executable reports PER-DEVICE figures
(verified against hand-computed examples), which is equivalent to the
global/(chips * peak) formulation. wire bytes come from the HLO census
(ring-model per-device traffic; bf16-adjusted for CPU convert hoisting).

MODEL_FLOPS = 6*N*D (train) or 2*N*D (inference), N = (active) params,
D = tokens — the "useful" fraction MODEL_FLOPS / (HLO_FLOPs * chips)
exposes remat recompute and padding waste.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

from ..core.cost_model import V5E, DevicePeaks


def model_flops(artifact: dict) -> Optional[float]:
    kind = artifact.get("kind")
    n = artifact.get("active_param_count") or artifact.get("param_count")
    if kind == "gram":
        # classical FLOPs of A^tA: m*n^2 MACs = 2*m*n^2 (upper bound ref)
        return 2.0 * artifact["m"] * artifact["n"] ** 2
    if not n:
        return None
    tokens = artifact["global_batch"] * (
        1 if kind == "decode" else artifact["seq_len"])
    per_token = 6.0 * n if kind == "train" else 2.0 * n
    return per_token * tokens


def roofline_terms(artifact: dict, hw: DevicePeaks = V5E) -> Dict:
    if artifact.get("status") != "ok":
        return {"cell": artifact.get("cell"), "status": artifact.get("status")}
    cost = artifact["cost"]
    chips = 1
    for s in artifact.get("mesh_shape", []):
        chips *= s
    if not artifact.get("mesh_shape"):
        chips = 512 if "2x16x16" in artifact.get("mesh", "") else 256

    corrected = artifact.get("cost_corrected") or {}
    flops_dev = corrected.get("flops") or cost.get("flops", 0.0)
    bytes_dev = corrected.get("bytes") or cost.get("bytes accessed", 0.0)
    sub = artifact.get("kernel_substitution")
    if sub:     # hand-written kernel FLOPs, counted analytically
        chips_tmp = 1
        for s in artifact.get("mesh_shape", []) or [512]:
            chips_tmp *= s
        flops_dev += sub["flops_global"] / chips_tmp
    coll = artifact.get("collectives_corrected") or artifact["collectives"]
    wire_dev = coll.get("wire_bytes_total_bf16adj",
                        coll.get("wire_bytes_total", 0.0))

    t_compute = flops_dev / hw.peak_flops
    t_memory = bytes_dev / hw.hbm_bw
    t_coll = wire_dev / hw.ici_bw
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)
    t_bound = max(terms.values())

    mf = model_flops(artifact)
    useful = (mf / (flops_dev * chips)) if (mf and flops_dev) else None
    t_model = (mf / (chips * hw.peak_flops)) if mf else None
    frac = (t_model / t_bound) if (t_model and t_bound > 0) else None

    mem = artifact["memory"]
    hbm_per_dev = (mem["argument_size_in_bytes"]
                   + mem["temp_size_in_bytes"]
                   + mem["output_size_in_bytes"]
                   - mem["alias_size_in_bytes"])
    return {
        "cell": artifact["cell"], "arch": artifact["arch"],
        "shape": artifact["shape"], "mesh": artifact["mesh"],
        "kind": artifact["kind"], "chips": chips,
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "t_bound_s": t_bound,
        "model_flops": mf, "hlo_flops_per_dev": flops_dev,
        "useful_flop_ratio": useful,
        "roofline_fraction": frac,
        "hbm_bytes_per_dev": hbm_per_dev,
        "fits_hbm": hbm_per_dev <= hw.hbm_bytes,
        "status": "ok",
    }


def load_artifacts(directory: str) -> List[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(directory, "*.json"))):
        with open(path) as f:
            out.append(json.load(f))
    return out


def _fmt_t(t):
    if t is None:
        return "-"
    if t >= 1:
        return f"{t:7.2f}s "
    if t >= 1e-3:
        return f"{t*1e3:7.2f}ms"
    return f"{t*1e6:7.1f}us"


def render_table(rows: List[dict]) -> str:
    head = (f"{'cell':<46} {'tCOMP':>9} {'tMEM':>9} {'tCOLL':>9} "
            f"{'dom':<6} {'useful':>7} {'roofl%':>7} {'HBM/dev':>8} fits")
    lines = [head, "-" * len(head)]
    for r in rows:
        if r.get("status") != "ok":
            lines.append(f"{r.get('cell', '?'):<46} {r.get('status')}")
            continue
        u = f"{r['useful_flop_ratio']*100:6.1f}%" if r["useful_flop_ratio"] else "      -"
        fr = f"{r['roofline_fraction']*100:6.1f}%" if r["roofline_fraction"] else "      -"
        lines.append(
            f"{r['cell']:<46} {_fmt_t(r['t_compute_s'])} "
            f"{_fmt_t(r['t_memory_s'])} {_fmt_t(r['t_collective_s'])} "
            f"{r['dominant']:<6} {u} {fr} "
            f"{r['hbm_bytes_per_dev']/2**30:7.2f}G "
            f"{'y' if r['fits_hbm'] else 'N'}")
    return "\n".join(lines)
