"""Three-term roofline from a compiled dry-run artifact (DESIGN.md section 8).

Peaks are keyed by ``device_kind`` (:data:`PEAKS`); a device not in the
table is an error, never a default.  TPU v5e per chip (Google Cloud
documentation, "TPU v5e"): 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM;
~50 GB/s per ICI link.  cost_analysis()/memory_analysis() are per-device
(the SPMD-partitioned program), so terms are per-chip directly:

    compute    = HLO_FLOPs / peak
    memory     = HLO_bytes_accessed / HBM_bw
    collective = per-device wire bytes (hlo_parse) / ICI link bw

The dominant term is the bottleneck the perf loop iterates on; the ratio
MODEL_FLOPS/(chips * HLO_FLOPs) exposes remat/redundancy waste; roofline
fraction = useful-compute time / dominant-term time.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import re

from .hlo_cost import analyze_hlo_text


def _cpu_bf16_artifact_bytes(hlo: str) -> float:
    """XLA-CPU has no native bf16 FMAs: it materializes fp32 twins of bf16
    weight stacks (hoisted out of the layer loop), which a TPU build never
    allocates.  Returns the largest such twin's bytes -- a conservative
    single-buffer adjustment to the reported peak (DESIGN.md section 8)."""
    bf16_param_dims = set()
    for m in re.finditer(r"=\s*bf16\[([0-9,]+)\][^=]*parameter\(", hlo):
        bf16_param_dims.add(m.group(1))
    # Distinct def sites: a gated MLP holds two such twins (wg, wu) live at
    # once, so sum the two largest distinct instruction outputs.
    sizes = []
    seen = set()
    for m in re.finditer(r"%([\w.\-]+)\s*=\s*f32\[([0-9,]+)\]", hlo):
        name, dims = m.group(1), m.group(2)
        if dims in bf16_param_dims and name not in seen:
            seen.add(name)
            n = 1
            for d in dims.split(","):
                n *= int(d)
            sizes.append(4.0 * n)
    sizes.sort(reverse=True)
    return float(sum(sizes[:2]))

__all__ = ["PEAKS", "peaks", "analyze_compiled", "roofline_terms",
           "format_row"]

#: Per-chip peaks by ``jax.Device.device_kind``.
PEAKS = {
    "TPU v5 lite": {
        "peak_flops": 197e12,     # bf16 / chip
        "hbm_bw": 819e9,          # bytes/s
        "ici_bw": 50e9,           # bytes/s/link
        "hbm_bytes": 16 * 1024**3,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table of ``device_kind``; raises for a device not in it."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[device_kind]


def analyze_compiled(compiled, n_devices: int, device_kind: str,
                     model_flops: Optional[float] = None) -> Dict[str, Any]:
    """Roofline record of ``compiled`` on ``n_devices`` chips of
    ``device_kind``."""
    hw = peaks(device_kind)
    # XLA's cost_analysis counts while bodies once; the loop-aware walker in
    # hlo_cost scales by trip count (and catches collectives inside scans).
    xla_cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    hc = analyze_hlo_text(hlo)
    wire, by_op = hc.wire, hc.wire_by_op

    flops = float(hc.flops)
    bytes_acc = float(hc.bytes)
    peak_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    artifact = _cpu_bf16_artifact_bytes(hlo)
    peak_tpu = max(peak_bytes - artifact, 0.0)

    out = {
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "collective_wire_bytes": wire,
        "collective_by_op": by_op,
        "xla_unscaled_flops": float(xla_cost.get("flops", 0.0)),
        "xla_unscaled_bytes": float(xla_cost.get("bytes accessed", 0.0)),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes": peak_bytes,
            "cpu_bf16_artifact_bytes": artifact,
            "peak_bytes_tpu": peak_tpu,
            "fits_hbm": bool(peak_tpu <= hw["hbm_bytes"]),
            "fits_hbm_raw_cpu": bool(peak_bytes <= hw["hbm_bytes"]),
        },
        "n_devices": n_devices,
    }
    out.update(roofline_terms(flops, bytes_acc, wire, device_kind))
    if model_flops:
        per_dev_useful = model_flops / n_devices
        out["model_flops"] = model_flops
        out["useful_ratio"] = (per_dev_useful / flops) if flops else 0.0
        out["useful_time_s"] = per_dev_useful / hw["peak_flops"]
        dom = out["dominant_time_s"]
        out["roofline_fraction"] = (out["useful_time_s"] / dom) if dom else 0.0
    return out


def roofline_terms(flops: float, bytes_acc: float, wire: float,
                   device_kind: str) -> Dict[str, Any]:
    hw = peaks(device_kind)
    t_c = flops / hw["peak_flops"]
    t_m = bytes_acc / hw["hbm_bw"]
    t_x = wire / hw["ici_bw"]
    terms = {"compute_s": t_c, "memory_s": t_m, "collective_s": t_x}
    dom = max(terms, key=terms.get)
    return {**terms, "dominant": dom.replace("_s", ""),
            "dominant_time_s": terms[dom]}


def format_row(name: str, r: Dict[str, Any]) -> str:
    return (f"| {name} | {r['compute_s']:.3e} | {r['memory_s']:.3e} | "
            f"{r['collective_s']:.3e} | {r['dominant']} | "
            f"{r.get('useful_ratio', 0):.3f} | "
            f"{r.get('roofline_fraction', 0):.3f} | "
            f"{r['memory']['peak_bytes'] / 2**30:.2f} GiB |")
