"""Assemble EXPERIMENTS.md tables from dry-run and svd artifacts.

    PYTHONPATH=src python -m benchmarks.report > results/experiments_tables.md
"""
from __future__ import annotations

import glob
import json
import os

from benchmarks import hw, roofline

RESULTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "results")


def dryrun_table(mesh: str) -> str:
    lines = [
        "| arch | shape | kind | n_micro | loss_chunks | lower (s) | "
        "compile (s) | args GB/chip | temp GB/chip | out GB/chip | "
        "collective GB/chip |",
        "|" + "---|" * 11,
    ]
    for path in sorted(glob.glob(os.path.join(RESULTS, "dryrun", "*.json"))):
        d = json.load(open(path))
        if d.get("mesh") != mesh:
            continue
        if "skipped" in d:
            lines.append(f"| {d['arch']} | {d['shape']} | skip | — | — | — | "
                         f"— | — | — | — | — |")
            continue
        if "error" in d:
            lines.append(f"| {d['arch']} | {d['shape']} | ERROR "
                         f"| | | | | | | | |")
            continue
        f = d["full"]
        coll = (d.get("composed", {}).get("collective_bytes_total")
                or f.get("collective_bytes_total", 0))
        lines.append(
            f"| {d['arch']} | {d['shape']} | {d['kind']} | "
            f"{d.get('n_micro', '—')} | {d.get('loss_chunks', '—')} | "
            f"{d.get('lower_s', 0)} | {f.get('compile_s', 0)} | "
            f"{f.get('argument_size_in_bytes', 0)/1e9:.2f} | "
            f"{f.get('temp_size_in_bytes', 0)/1e9:.2f} | "
            f"{f.get('output_size_in_bytes', 0)/1e9:.2f} | "
            f"{coll/1e9:.2f} |")
    return "\n".join(lines)


def svd_table() -> str:
    path = os.path.join(RESULTS, "svd_dryrun.json")
    if not os.path.exists(path):
        return "(svd_dryrun.json not generated yet)"
    d = json.load(open(path))
    lines = ["| variant | GFLOPs/chip | bytes GB/chip | collective MB/chip | "
             "t_comp (ms) | t_coll (ms) | collectives |",
             "|" + "---|" * 7]
    for tag, r in d.items():
        coll = r.get("collective_bytes_total", 0)
        fl = r.get("flops", 0)
        by = r.get("bytes_accessed", 0)
        kinds = {k: round(v / 1e6, 1)
                 for k, v in r.get("collective_bytes", {}).items() if v}
        lines.append(
            f"| {tag} | {fl/1e9:.1f} | {by/1e9:.2f} | {coll/1e6:.1f} | "
            f"{fl/hw.PEAK_FLOPS*1e3:.2f} | {coll/hw.ICI_BW*1e3:.2f} | "
            f"{kinds} |")
    return "\n".join(lines)


def main():
    print("## §Dry-run — single-pod (16x16 = 256 chips)\n")
    print(dryrun_table("single"))
    print("\n## §Dry-run — multi-pod (2x16x16 = 512 chips)\n")
    print(dryrun_table("multi"))
    print("\n## §Roofline — single-pod\n")
    cells = roofline.load_cells()
    print(roofline.fmt_table(cells, "single"))
    print("\n## §Roofline — multi-pod\n")
    print(roofline.fmt_table(cells, "multi"))
    print("\n## §Perf — SVD power-step variants (paper 1TB dense problem)\n")
    print(svd_table())


if __name__ == "__main__":
    main()
