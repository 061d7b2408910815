"""Roofline analysis over dry-run artifacts (§Roofline of EXPERIMENTS.md).

Per (arch × shape × mesh) cell, derive from the loop-corrected HLO
analysis:

    compute term    = HLO_FLOPs_per_device / peak_FLOPs
    memory term     = HLO_bytes_per_device / HBM_bw
    collective term = collective_bytes_per_device / link_bw

Hardware constants come from :data:`PEAKS`, one entry per chip keyed by
JAX's ``device_kind``; the dry-run cells target a v5e pod, so the rows
use that kind's peaks.  The dominant term is the step-time lower bound; the
roofline fraction = compute / max(terms) is the MFU-like score the perf
loop drives up.  MODEL_FLOPS (6·N·D dense / 6·N_active·D MoE) over
HLO_FLOPs exposes remat/redundancy waste.

Biases (documented, consistent across cells): HLO bytes use the
fusion-boundary model on CPU-backend HLO — TPU fuses more aggressively,
so the memory term is an upper bound; collective bytes use op output
size (all-gather counts the gathered tensor).
"""

from __future__ import annotations

import argparse
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

from ..obs import configure_logging, get_logger, log_event

# Published per-chip peaks keyed by ``jax.devices()[0].device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s of ICI over 4 links (50 GB/s each).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}
TARGET_DEVICE_KIND = "TPU v5 lite"  # the production mesh is a v5e pod


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and bytes/s of one chip of ``device_kind``; a kind
    that is not in :data:`PEAKS` is an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}"
        )
    return PEAKS[device_kind]

logger = get_logger("launch.roofline")

__all__ = ["PEAKS", "peaks", "model_flops", "roofline_row", "build_table", "main"]


def model_flops(meta: dict, kind: str, n_devices: int) -> Optional[float]:
    """Analytic useful-FLOPs per device (6ND convention)."""
    if kind == "train" and "tokens_per_step" in meta:
        return 6.0 * meta["active_param_count"] * meta["tokens_per_step"] / n_devices
    if kind == "prefill":
        return 2.0 * meta["active_param_count"] * meta["tokens_per_step"] / n_devices
    if kind == "decode":
        return 2.0 * meta["active_param_count"] * meta["tokens_per_step"] / n_devices
    if kind == "train" and "n_edges" in meta:  # GNN: projection-dominated
        return None  # reported as n/a: no community-standard 6ND analogue
    if kind == "cluster":
        n, d, f = meta["n_points"], meta["dim"], meta["frontier"]
        return 2.0 * n * d * f / n_devices  # the range-count matmul
    return None


@dataclass
class Row:
    arch: str
    shape: str
    mesh: str
    status: str
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    bound: str = ""
    mem_gib: float = 0.0
    hlo_flops: float = 0.0
    model_flops: Optional[float] = None
    flops_ratio: Optional[float] = None
    roofline_fraction: float = 0.0
    note: str = ""

    def as_dict(self):
        return self.__dict__.copy()


def roofline_row(rec: dict) -> Row:
    if rec.get("status") == "skip":
        return Row(rec["arch"], rec["shape"], rec["mesh"], "skip", note=rec.get("reason", ""))
    if rec.get("status") != "ok":
        return Row(rec["arch"], rec["shape"], rec["mesh"], "error",
                   note=rec.get("error", "")[:120])
    h = rec["hlo_analysis"]
    variant = rec.get("meta", {}).get("variant")
    shape_label = rec["shape"] + (f" ({variant})" if variant else "")
    flops = h["flops"]
    mem_bytes = h["bytes_accessed"]
    coll = h["collectives"].get("total", {}).get("bytes", 0.0)
    pk = peaks(TARGET_DEVICE_KIND)
    ct = flops / pk["flops"]
    mt = mem_bytes / pk["hbm_bw"]
    lt = coll / pk["link_bw"]
    terms = {"compute": ct, "memory": mt, "collective": lt}
    bound = max(terms, key=terms.get)
    mf = model_flops(rec.get("meta", {}), rec.get("meta", {}).get("kind", ""), rec["n_devices"])
    return Row(
        rec["arch"], shape_label, rec["mesh"], "ok",
        compute_s=ct, memory_s=mt, collective_s=lt, bound=bound,
        mem_gib=rec["memory_analysis"]["bytes_per_device"]["total"] / 2**30,
        hlo_flops=flops, model_flops=mf,
        flops_ratio=(mf / flops) if (mf and flops) else None,
        roofline_fraction=(ct / max(terms.values())) if max(terms.values()) > 0 else 0.0,
    )


def improvement_hint(row: Row) -> str:
    if row.bound == "collective":
        return ("reduce re-gather traffic: bf16 collectives, fewer remat-induced "
                "all-gathers, overlap with compute")
    if row.bound == "memory":
        return ("fuse the softmax/score chain (Pallas flash kernel on TPU) / "
                "cut fp32 intermediates")
    return "increase arithmetic intensity (larger tiles/batch) or cut remat recompute"


def build_table(art_dir: Path) -> Dict[str, List[Row]]:
    out: Dict[str, List[Row]] = {}
    for mesh_dir in sorted(art_dir.iterdir()):
        if not mesh_dir.is_dir():
            continue
        rows = []
        for f in sorted(mesh_dir.glob("*.json")):
            rows.append(roofline_row(json.loads(f.read_text())))
        out[mesh_dir.name] = rows
    return out


def to_markdown(rows: List[Row], mesh: str) -> str:
    lines = [
        f"### Mesh {mesh}",
        "",
        "| arch | shape | compute s | memory s | collective s | bound | "
        "roofline frac | mem GiB/dev | MODEL/HLO flops | note |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r.status == "skip":
            lines.append(f"| {r.arch} | {r.shape} | — | — | — | skip | — | — | — | {r.note[:60]} |")
            continue
        if r.status == "error":
            lines.append(f"| {r.arch} | {r.shape} | — | — | — | ERROR | — | — | — | {r.note[:60]} |")
            continue
        ratio = f"{r.flops_ratio:.2f}" if r.flops_ratio else "n/a"
        lines.append(
            f"| {r.arch} | {r.shape} | {r.compute_s:.3f} | {r.memory_s:.3f} | "
            f"{r.collective_s:.3f} | {r.bound} | {r.roofline_fraction:.2f} | "
            f"{r.mem_gib:.1f} | {ratio} | {improvement_hint(r)[:60]} |"
        )
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--artifacts", default="artifacts/dryrun")
    ap.add_argument("--out", default="artifacts/roofline")
    ap.add_argument("--quiet", action="store_true",
                    help="write artifacts only; no table on the console")
    args = ap.parse_args()
    configure_logging(quiet=args.quiet)
    art = Path(args.artifacts)
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    tables = build_table(art)
    md_parts, js = [], {}
    for mesh, rows in tables.items():
        md_parts.append(to_markdown(rows, mesh))
        js[mesh] = [r.as_dict() for r in rows]
        ok = [r for r in rows if r.status == "ok"]
        if ok:
            worst = min(ok, key=lambda r: r.roofline_fraction)
            coll = max(ok, key=lambda r: r.collective_s)
            md_parts.append(
                f"\nworst roofline fraction: **{worst.arch}:{worst.shape}** "
                f"({worst.roofline_fraction:.2f}); most collective-bound: "
                f"**{coll.arch}:{coll.shape}** ({coll.collective_s:.1f}s)\n"
            )
            log_event(
                logger, "roofline_mesh", mesh=mesh, cells=len(rows),
                worst_cell=f"{worst.arch}:{worst.shape}",
                worst_fraction=round(worst.roofline_fraction, 3),
                most_collective=f"{coll.arch}:{coll.shape}",
                collective_s=round(coll.collective_s, 2),
            )
    (out_dir / "roofline.md").write_text("\n\n".join(md_parts))
    (out_dir / "roofline.json").write_text(json.dumps(js, indent=2))
    if logger.isEnabledFor(20):  # the table itself is INFO-level output
        logger.info("roofline table\n%s", "\n\n".join(md_parts))
    log_event(logger, "roofline_written",
              md=str(out_dir / "roofline.md"), json=str(out_dir / "roofline.json"))


if __name__ == "__main__":
    main()
