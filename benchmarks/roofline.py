"""Roofline analysis from the dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch × shape) on the single-pod 16x16 mesh:
    compute term    = HLO_FLOPs / peak_FLOP/s            [per chip]
    memory term     = HLO_bytes / HBM_bw                 [per chip]
    collective term = collective_bytes / link_bw         [per chip]
plus MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE), the useful-compute
ratio, the dominant bottleneck, and a one-line improvement note.

HLO figures use the depth-extrapolated values (HLO cost analysis counts
while-loop bodies once; see launch/dryrun.py). The peaks come from
``PEAKS``, keyed by the ``device_kind`` each record names (the kind of the
devices the dry run compiled for); a kind that is not in the table raises
instead of borrowing another chip's numbers.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

#: Published per-chip peaks by ``jax.Device.device_kind``.
#: "TPU v5 lite" — Google Cloud documentation, "TPU v5e": 197 TFLOP/s
#: bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s": 1600e9 / 8},
}


def peaks(device_kind: Optional[str]) -> Dict[str, float]:
    """The peak table row for a device kind; unknown kinds raise."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

RESULTS = os.path.join(os.path.dirname(__file__), "results", "dryrun.json")


def model_flops(rec: Dict) -> float:
    """6·N·D per chip for a train step; 2·N·D for forward-only serving
    (prefill: D = batch·seq tokens; decode: D = batch·1 new tokens)."""
    n_active = rec.get("active_params") or rec["params"]
    if rec["kind"] == "train":
        tokens = rec["global_batch"] * rec["seq_len"]
        total = 6.0 * n_active * tokens
    elif rec["kind"] == "prefill":
        tokens = rec["global_batch"] * rec["seq_len"]
        total = 2.0 * n_active * tokens
    else:  # decode: one token per sequence
        total = 2.0 * n_active * rec["global_batch"]
    return total / rec["chips"]


def analyze(rec: Dict) -> Optional[Dict]:
    if "error" in rec:
        return None
    flops = rec.get("flops_extrapolated") or rec.get("flops_per_device")
    bts = rec.get("bytes_extrapolated") or rec.get("bytes_accessed_per_device")
    coll = rec.get("collective_bytes_extrapolated")
    if coll is None:
        coll = rec.get("collectives", {}).get("total_bytes", 0)
    if flops is None or bts is None:
        return None
    peak = peaks(rec.get("device_kind"))
    t_c = flops / peak["flops"]
    t_m = bts / peak["hbm_bytes_per_s"]
    t_x = coll / peak["ici_bytes_per_s"]
    dom = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))[1]
    mf = model_flops(rec)
    t_total = max(t_c, t_m, t_x)
    # achievable fraction of the compute roofline for USEFUL flops:
    frac = (mf / peak["flops"]) / t_total if t_total > 0 else 0.0
    return {
        "arch": rec["arch"], "shape": rec["shape"], "chips": rec["chips"],
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
        "dominant": dom,
        "model_flops_per_chip": mf,
        "useful_ratio": mf / flops if flops else 0.0,
        "roofline_fraction": frac,
        "temp_gb": rec.get("temp_size_in_bytes", 0) / 2 ** 30,
        "microbatches": rec.get("microbatches", 1),
    }


NOTES = {
    "compute": "shave non-useful FLOPs (remat policy, causal-waste, padding)",
    "memory": "fuse/shrink fp32 intermediates; raise arithmetic intensity "
              "(bigger per-chip tiles, fewer passes over activations)",
    "collective": "resharding schedule: fewer/lower-precision all-reduces, "
                  "reduce-scatter fusion, EP/TP axis re-balance",
}


def load(path: str = RESULTS) -> List[Dict]:
    with open(path) as f:
        return json.load(f)


def table(records: List[Dict], chips: int = 256) -> List[Dict]:
    rows = []
    for rec in records:
        if rec.get("chips") != chips:
            continue
        row = analyze(rec)
        if row:
            row["note"] = NOTES[row["dominant"]]
            rows.append(row)
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    return rows


def main():
    if not os.path.exists(RESULTS):
        print("roofline,skipped,0,no dryrun.json yet — run "
              "python -m repro.launch.dryrun first")
        return
    rows = table(load())
    for r in rows:
        print(f"roofline,{r['arch']}|{r['shape']},"
              f"{max(r['compute_s'], r['memory_s'], r['collective_s']) * 1e6:.0f},"
              f"c/m/x={r['compute_s'] * 1e3:.2f}/{r['memory_s'] * 1e3:.2f}/"
              f"{r['collective_s'] * 1e3:.2f}ms dom={r['dominant']} "
              f"useful={r['useful_ratio'] * 100:.0f}% "
              f"roofline={r['roofline_fraction'] * 100:.1f}%")


if __name__ == "__main__":
    main()
