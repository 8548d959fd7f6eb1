"""Spatial-parallelization pass (paper §III-A "Spatial Parallelization").

Each partition's operator chain is replicated P ∈ {2^n} times; we run an
exhaustive search for the smallest per-target P that satisfies the target
throughput, minimizing resource use — exactly the paper's scheme, driven
by an analytic throughput model instead of HLS reports.

TPU reinterpretation (DESIGN.md §2 A5): replicas process independent
*events*, so P maps to the event micro-batch width a segment consumes per
step. Segments with smaller P process the pipeline micro-batch in
``B/P`` sequential chunks (a hardware replica draining a stream); the
executor realizes this with ``lax.scan`` over chunks, so the choice is
both faithful and actually executable/benchmarkable.

Cost model per op (per event): peak-normalized max(compute, memory) with a
size-derived MXU efficiency factor (small matrices underfill the 128×128
systolic array — the TPU analogue of the paper's observation that loop
overhead dominates tiny AIE kernels). Weights are VMEM-resident and
amortized across the micro-batch; activations stream per event. The
per-op-type formulas are declared on the op registry specs
(``OpSpec.cost`` / ``OpSpec.mxu_eff`` in ``core/op_registry.py``); this
pass only interprets them.
"""
from __future__ import annotations

import dataclasses
import math

from repro.core.graph_ir import Graph
from repro.core.op_registry import default_cost, require_spec
from repro.launch import mesh as hw

VPU_PEAK = 4e12  # v5e vector unit, FLOP/s (non-MXU ops)


@dataclasses.dataclass
class Requirements:
    """The design flow's second input (paper: 'a set of hardware
    requirements such as the target throughput and platform')."""
    target_throughput: float = 1.0e6     # events / s / replica-group
    max_latency_s: float | None = None   # trigger budget (paper: 10 µs)
    platform: str = "tpu"                # 'tpu' | 'cpu'
    # jax.Device.device_kind: picks the chip's peaks; required on 'tpu'
    device_kind: str | None = None
    design_point: int = 3                # ① ② ③
    n_hits: int = 128                    # graph size per event
    precision_policy: str = "mixed"      # 'fp' | 'mixed' (paper: 16b/8b)
    tpu_native_gravnet: bool = False     # beyond-paper partitioning
    max_p: int = 256


def op_cost(op, n_hits: int, *, precision_bytes: float = 1.0):
    """(flops, act_bytes, weight_bytes) per event, from the op's
    registered cost hook."""
    cost = require_spec(op).cost or default_cost
    return cost(op, n_hits, precision_bytes)


def _mxu_efficiency(op, n_rows: int, n_hits: int = 128) -> float:
    """Fraction of MXU peak a matmul of this size can use."""
    eff = require_spec(op).mxu_eff
    return eff(op, n_rows, n_hits) if eff is not None else 1.0


def segment_time(ops, n_hits: int, p: int, platform: str = "tpu",
                 device_kind: str | None = None) -> float:
    """Seconds for one segment step processing p events."""
    if platform == "tpu":
        peaks = hw.chip_peaks(device_kind)
        peak_mxu, peak_vpu, bw = peaks.flops_bf16, VPU_PEAK, peaks.hbm_bw
    else:  # calibrated-order-of-magnitude CPU constants (relative use only)
        peak_mxu = peak_vpu = 5e10
        bw = 2e10
    t = 0.0
    for op in ops:
        flops, act, wb = op_cost(op, n_hits)
        is_mm = require_spec(op).mxu_matmul and op.target == "mxu"
        eff = _mxu_efficiency(op, n_hits * p, n_hits) if is_mm else 1.0
        peak = peak_mxu if is_mm else peak_vpu
        t_compute = p * flops / (eff * peak)
        t_mem = (p * act + wb) / bw
        t += max(t_compute, t_mem) + 1e-7  # fixed per-op issue overhead
    return t


def parallelize(g: Graph, req: Requirements) -> Graph:
    """Pick the smallest (P_mxu, P_xla) meeting the throughput target."""
    g = g.clone()
    segs: dict[int, list] = {}
    for op in g:
        segs.setdefault(op.segment or 0, []).append(op)

    def model(p_mxu: int, p_xla: int):
        # Versal runs segments as concurrent spatial pipeline stages; on a
        # single TPU chip (and on CPU) segments serialize, so throughput is
        # micro-batch / TOTAL time (DESIGN.md §2 A5), and the total IS the
        # per-event decision latency the trigger budget constrains.
        # Cross-stage pipelining returns at pod scale via data replicas.
        b = max(p_mxu, p_xla)  # pipeline micro-batch width
        total = 0.0
        for ops in segs.values():
            tgt = ops[0].target
            p = p_mxu if tgt == "mxu" else p_xla
            chunks = b // p
            total += chunks * segment_time(ops, req.n_hits, p, req.platform,
                                           req.device_kind)
        return (b / total if total > 0 else float("inf")), total

    max_lat = req.max_latency_s or float("inf")
    pows = [2 ** i for i in range(int(math.log2(req.max_p)) + 1)]
    best = None
    fallback = None
    for p_mxu in pows:
        for p_xla in pows:
            if max(p_mxu, p_xla) % min(p_mxu, p_xla) != 0:
                continue
            tp, lat = model(p_mxu, p_xla)
            if lat <= max_lat and (fallback is None or tp > fallback[3]):
                fallback = (p_mxu + p_xla, p_mxu, p_xla, tp, lat)
            if tp >= req.target_throughput and lat <= max_lat:
                cost = p_mxu + p_xla  # resource proxy (paper: minimize P)
                if best is None or cost < best[0]:
                    best = (cost, p_mxu, p_xla, tp, lat)
    if best is None:
        # target unreachable within the latency budget: best-throughput
        # latency-feasible point (or P=1 if even that busts the budget)
        best = fallback or (2, 1, 1) + model(1, 1)
    _, p_mxu, p_xla, tp, lat = best
    for op in g:
        op.attrs_opt["P"] = p_mxu if op.target == "mxu" else p_xla
    g.meta["parallelization"] = {
        "P_mxu": p_mxu, "P_xla": p_xla, "microbatch": max(p_mxu, p_xla),
        "model_throughput_ev_s": tp, "model_latency_s": lat,
        "target": req.target_throughput, "max_latency_s": max_lat,
    }
    return g
