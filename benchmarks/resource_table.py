"""Table I analogue: per-design resource utilization.

The paper reports FF/LUT/DSP/BRAM + AIE tile/compute/memory utilization.
The TPU resource vector: per-segment FLOPs/event, activation bytes/event,
weight bytes, VMEM working set (vs the 128 MiB v5e budget), segment count
per target, and the parallelization factors — emitted per design point
for both detector variants.
"""
from __future__ import annotations

import jax

from benchmarks.common import row
from repro.core import caloclusternet as ccn
from repro.core.passes.parallelize import Requirements
from repro.core.pipeline import deploy
from repro.data.belle2 import Belle2Config, generate
from repro.launch.mesh import V5E


def run():
    rows = []
    for detector, cfg, gen in (
            ("current", ccn.current_detector_config(),
             Belle2Config(n_crystals=576, grid=(24, 24), n_hits=32,
                          noise_rate=8.0)),
            ("upgrade", ccn.CCNConfig(), Belle2Config())):
        params = ccn.init(jax.random.PRNGKey(0), cfg)
        graph = ccn.to_graph(params, cfg)
        data = generate(gen, 32, seed=3)
        calib = {"hits": data["feats"], "mask": data["mask"]}
        for dp in (1, 2, 3):
            req = Requirements(design_point=dp, platform="tpu",
                               device_kind=V5E,
                               precision_policy="mixed",
                               n_hits=cfg.n_hits, target_throughput=3e6,
                               max_latency_s=10e-6)
            pipe = deploy(graph, req, calibration_feeds=calib,
                          kernel_backend="xla")
            rep = pipe.resource_report()
            tot_fl = sum(r["flops_per_event"] for r in rep)
            tot_vmem = sum(r["vmem_working_set"] for r in rep)
            mxu_segs = sum(1 for r in rep if r["target"] == "mxu")
            xla_segs = len(rep) - mxu_segs
            int8_ops = sum(1 for op in pipe.graph
                           if op.precision == "int8")
            rows.append(row(
                f"tableI_design{dp}_{detector}",
                pipe.model_latency() * 1e6,
                f"segments={len(rep)} (mxu={mxu_segs} xla={xla_segs}) "
                f"P={pipe.par['P_mxu']}/{pipe.par['P_xla']} "
                f"flops/ev={tot_fl:,.0f} "
                f"vmem={tot_vmem / (1 << 20):.2f}MiB "
                f"({100 * tot_vmem / (128 << 20):.1f}% of v5e) "
                f"int8_ops={int8_ops}"))
    return rows


if __name__ == "__main__":
    run()
