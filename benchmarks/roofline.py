"""§Roofline: three-term analysis per (arch x shape x mesh) from the
dry-run artifacts in experiments/dryrun/*.json.

  compute term    = HLO_FLOPs / peak_FLOP/s          (per chip)
  memory term     = HLO_bytes / HBM_bw               (per chip)
  collective term = collective_bytes / link_bw       (per chip)

(the dry-run records trip-count-corrected per-device values, so the
"/chips" in the brief's formulas is already applied).  Hardware: TPU v5e —
197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import glob
import json
import os

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9
HBM_PER_CHIP = 16e9  # v5e


def model_flops_per_device(rec: dict) -> float:
    """MODEL_FLOPS = 6*N_active*D (train) or 2*N_active*D (fwd-only),
    per device."""
    from repro.configs import get_config
    cfg = get_config(rec["arch"])
    n_active = active_params(cfg, rec["n_params"])
    if rec["kind"] == "train":
        tokens = {"train_4k": 256 * 4096}.get(rec["shape"], 0)
        factor = 6.0
    elif rec["kind"] == "prefill":
        tokens = 32 * 32768
        factor = 2.0
    else:  # decode: one token per sequence
        tokens = {"decode_32k": 128, "long_500k": 1}.get(rec["shape"], 1)
        factor = 2.0
    return factor * n_active * tokens / rec["n_devices"]


def active_params(cfg, n_total: int) -> float:
    if not getattr(cfg, "moe", False):
        return float(n_total)
    L_moe = cfg.n_layers - cfg.first_dense_layers
    routed = L_moe * cfg.n_experts * 3 * cfg.d_model * cfg.moe_d_ff
    return float(n_total - routed * (1 - cfg.top_k / cfg.n_experts))


def analyze(rec: dict) -> dict:
    comp = rec["flops"] / PEAK_FLOPS
    # memory term from the fused-floor bytes (TPU-like fusion); the raw
    # upper bound is recorded alongside (see DESIGN.md §8 caveats)
    memt = rec.get("bytes_floor", rec["bytes_accessed"]) / HBM_BW
    coll = rec["collective_bytes"].get("total", 0.0) / LINK_BW
    terms = {"compute_s": comp, "memory_s": memt, "collective_s": coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops_per_device(rec)
    ideal = mf / PEAK_FLOPS
    frac = ideal / max(terms.values()) if max(terms.values()) > 0 else 0.0
    peak = rec["memory"].get("peak_bytes") or 0
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        **{k: round(v, 6) for k, v in terms.items()},
        "dominant": dominant.replace("_s", ""),
        "model_flops": mf,
        "useful_ratio": round(mf / rec["flops"], 4) if rec["flops"] else 0.0,
        "roofline_fraction": round(frac, 4),
        "memory_s_upper": round(rec["bytes_accessed"] / HBM_BW, 6),
        "peak_bytes_per_dev": peak,
        "fits_hbm": bool(peak and peak <= HBM_PER_CHIP),
    }


def main(dryrun_dir: str = "experiments/dryrun"):
    rows = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("skipped"):
            continue
        try:
            rows.append(analyze(rec))
        except Exception as e:  # record parse issues, don't die
            rows.append({"arch": rec.get("arch"), "shape": rec.get("shape"),
                         "mesh": rec.get("mesh"), "error": str(e)})
    hdr = ["arch", "shape", "mesh", "compute_s", "memory_s", "collective_s",
           "dominant", "useful_ratio", "roofline_fraction", "fits_hbm"]
    out = [",".join(hdr)]
    for r in rows:
        out.append(",".join(str(r.get(k, "")) for k in hdr))
    csv = "\n".join(out)
    print(csv)
    os.makedirs("experiments", exist_ok=True)
    with open("experiments/roofline.csv", "w") as f:
        f.write(csv + "\n")
    with open("experiments/roofline_full.json", "w") as f:
        json.dump(rows, f, indent=1, default=float)
    return rows


if __name__ == "__main__":
    main()
