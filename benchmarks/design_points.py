"""Fig 5a/5b analogue: latency + throughput for designs ①②③ vs the
no-flow baseline, measured on CPU XLA + derived for TPU v5e from the
analytic pipeline model.

Paper claims to reproduce (ordering/shape, §IV):
  - design ① is SLOWER than the baseline (heterogeneous-partitioning
    overhead: per-segment dispatch, no cross-boundary fusion);
  - design ② recovers with fusion + spatial parallelization;
  - design ③ is fastest (kernel-level optimization at identical
    resource allocation — here: flattened kernels, retile cancellation,
    int8 chaining, whole-pipeline jit).
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import row, time_fn
from repro.core import caloclusternet as ccn
from repro.core.passes.parallelize import Requirements
from repro.core.pipeline import deploy
from repro.data.belle2 import Belle2Config, generate
from repro.launch.mesh import V5E

N_EVENTS = 256


def run(detector: str = "upgrade", events: int = N_EVENTS):
    if detector == "current":
        cfg = ccn.current_detector_config()
        gen = Belle2Config(n_crystals=576, grid=(24, 24), n_hits=32,
                           noise_rate=8.0)
    else:
        cfg = ccn.CCNConfig()
        gen = Belle2Config()
    params = ccn.init(jax.random.PRNGKey(0), cfg)
    data = generate(gen, events, seed=11)
    feeds = {"hits": data["feats"], "mask": data["mask"]}
    calib = {"hits": data["feats"][:32], "mask": data["mask"][:32]}
    graph = ccn.to_graph(params, cfg)
    rows = []

    # no-flow baseline (the GPU/TensorRT reference analogue): direct jit
    @jax.jit
    def baseline(h, m):
        out = ccn.apply(params, h, m, cfg)
        return ccn.cps(out, m, cfg)

    t, _ = time_fn(lambda: baseline(feeds["hits"], feeds["mask"]), iters=3)
    rows.append(row(f"fig5_baseline_xla_{detector}",
                    t / events * 1e6,
                    "no-flow fp32 reference"))

    base_ev_s = events / t
    for dp in (1, 2, 3):
        req = Requirements(design_point=dp, platform="cpu",
                           precision_policy="mixed", n_hits=cfg.n_hits,
                           target_throughput=5e4, max_latency_s=2e-3)
        pipe = deploy(graph, req, calibration_feeds=calib)
        t, _ = time_fn(lambda: pipe(feeds), iters=3)
        ev_s = events / t
        # derived TPU numbers from the analytic model (per chip)
        req_tpu = Requirements(design_point=dp, platform="tpu",
                               device_kind=V5E,
                               precision_policy="mixed",
                               n_hits=cfg.n_hits, target_throughput=3e6,
                               max_latency_s=10e-6)
        pipe_tpu = deploy(graph, req_tpu, calibration_feeds=calib,
                          kernel_backend="xla")
        rows.append(row(
            f"fig5_design{dp}_{detector}", t / events * 1e6,
            f"cpu {ev_s:,.0f} ev/s ({ev_s / base_ev_s:.2f}x baseline); "
            f"tpu-model {pipe_tpu.model_throughput():,.0f} ev/s/chip "
            f"lat {pipe_tpu.model_latency() * 1e6:.2f} us (<=10us) "
            f"P={pipe_tpu.par['P_mxu']}/{pipe_tpu.par['P_xla']}"))

    # beyond-paper: TPU-native gravnet partitioning at design ③
    req = Requirements(design_point=3, platform="cpu",
                       precision_policy="mixed", n_hits=cfg.n_hits,
                       target_throughput=5e4, max_latency_s=2e-3,
                       tpu_native_gravnet=True)
    pipe = deploy(graph, req, calibration_feeds=calib)
    t, _ = time_fn(lambda: pipe(feeds), iters=3)
    req_tpu = Requirements(design_point=3, platform="tpu", device_kind=V5E,
                           precision_policy="mixed", n_hits=cfg.n_hits,
                           target_throughput=3e6, max_latency_s=10e-6,
                           tpu_native_gravnet=True)
    pipe_tpu = deploy(graph, req_tpu, calibration_feeds=calib,
                      kernel_backend="xla")
    rows.append(row(
        f"fig5_design3_tpunative_{detector}", t / events * 1e6,
        f"cpu {events / t:,.0f} ev/s; tpu-model "
        f"{pipe_tpu.model_throughput():,.0f} ev/s/chip "
        f"lat {pipe_tpu.model_latency() * 1e6:.2f} us"))
    return rows


if __name__ == "__main__":
    run()
