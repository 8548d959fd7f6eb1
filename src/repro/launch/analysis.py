"""Compiled-artifact analysis: cost terms, collective bytes, roofline.

Sources (EXPERIMENTS.md §Roofline):
- ``compiled.cost_analysis()``  -> HLO FLOPs + bytes accessed
- ``compiled.as_text()``        -> post-SPMD HLO; collective bytes are the
  summed output sizes of all-gather / all-reduce / reduce-scatter /
  all-to-all / collective-permute ops (per-device shapes after
  partitioning).

Scan caveat (measured, see EXPERIMENTS.md §Methodology): XLA cost analysis
counts a while/scan body ONCE. Architectures whose layer loop is a python
loop (all GNNs, MIND, CaloClusterNet) are exact. LM archs lower scan-free
cost variants at n_layers ∈ {2,4}; F(L) is affine in L, so
F_full = F(2) + (F(4)-F(2))/2 · (L-2). The same composition applies to
bytes and collective bytes.
"""
from __future__ import annotations

import re

from repro.launch import mesh as hw

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> float:
    """'bf16[8,128]' -> bytes; handles tuple results '(f32[2], s32[2])'."""
    total = 0.0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum output bytes per collective kind from post-SPMD HLO text."""
    out = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # '%x = bf16[..]{..} all-gather(' / ' ROOT %y = (f32[..]) all-reduce('
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", s)
        if not m:
            continue
        op = m.group(2)
        # strip -start/-done fusion suffixes (async collectives)
        base = op.replace("-start", "").replace("-done", "")
        if base in _COLLECTIVES:
            if op.endswith("-done"):
                continue  # counted at -start
            out[base] += _shape_bytes(m.group(1))
            counts[base] += 1
    out["total_bytes"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = counts
    return out


def cost_terms(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, list):  # some backends return [dict]
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    colls = parse_collectives(compiled.as_text())
    return {"flops": flops, "bytes": byts,
            "collective_bytes": colls["total_bytes"],
            "collectives": colls}


def affine_extrapolate(t2: dict, t4: dict, l_full: int) -> dict:
    """F(L) = a + b·L from L=2, L=4 measurements."""
    out = {}
    for k in ("flops", "bytes", "collective_bytes"):
        b = (t4[k] - t2[k]) / 2.0
        a = t2[k] - 2.0 * b
        out[k] = a + b * l_full
    return out


def roofline(terms: dict, *, n_chips: int, model_flops: float,
             device_kind: str) -> dict:
    """Three-term roofline (seconds) + dominant bottleneck.

    FLOPs/bytes from cost_analysis are whole-program totals of the SPMD
    module (per-device work × … XLA reports the module as lowered — on
    the CPU backend the SPMD module is per-device, so divide by nothing;
    totals here treat cost_analysis as PER-DEVICE work and multiply terms
    accordingly — see EXPERIMENTS.md §Methodology for validation).
    """
    peaks = hw.chip_peaks(device_kind)
    t_compute = terms["flops"] / peaks.flops_bf16
    t_memory = terms["bytes"] / peaks.hbm_bw
    t_coll = terms["collective_bytes"] / peaks.ici_bw
    dominant = max(
        (("compute", t_compute), ("memory", t_memory),
         ("collective", t_coll)), key=lambda kv: kv[1])[0]
    step_time = max(t_compute, t_memory, t_coll)
    useful = model_flops / max(terms["flops"] * n_chips, 1.0)
    mfu = (model_flops / n_chips / max(step_time, 1e-12)
           ) / peaks.flops_bf16
    return {
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "step_time_s": step_time,
        "model_flops": model_flops,
        "useful_flops_ratio": useful,
        "roofline_fraction_mfu": mfu,
    }
