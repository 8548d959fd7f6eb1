"""Spatial-parallelization sweep (paper §III-A): throughput vs P.

The paper exhaustively searches P ∈ {2^n} for the smallest factor meeting
the target. This reproduces the search curve: analytic throughput model
per P (TPU) + measured CPU events/s at the corresponding micro-batch.
"""
from __future__ import annotations

import jax

from benchmarks.common import row, time_fn
from repro.core import caloclusternet as ccn
from repro.core.passes import fuse, partition
from repro.core.passes.mapping import map_templates
from repro.core.passes.parallelize import Requirements, parallelize
from repro.core.pipeline import CompiledPipeline, deploy
from repro.core.quantization import apply_precision_policy
from repro.data.belle2 import Belle2Config, generate
from repro.launch.mesh import V5E


def run(max_p: int = 32):
    cfg = ccn.CCNConfig()
    params = ccn.init(jax.random.PRNGKey(0), cfg)
    graph = ccn.to_graph(params, cfg)
    gen = Belle2Config()
    data = generate(gen, 128, seed=5)
    feeds = {"hits": data["feats"], "mask": data["mask"]}
    rows = []
    g0 = map_templates(apply_precision_policy(
        partition(fuse(graph)), policy="fp"))
    p = 1
    while p <= max_p:
        req = Requirements(design_point=3, platform="cpu",
                           precision_policy="fp", n_hits=cfg.n_hits,
                           max_p=p, target_throughput=1e12)  # force P=max
        gp = parallelize(g0, req)
        from repro.core.passes.kernel_opt import kernel_optimize
        gk = kernel_optimize(gp, n_rows=cfg.n_hits)
        pipe = CompiledPipeline(gk, req, "xla")
        t, _ = time_fn(lambda: pipe(feeds))
        ev_s = 128 / t
        # analytic TPU throughput at this P
        req_t = Requirements(design_point=3, platform="tpu", device_kind=V5E,
                             precision_policy="fp", n_hits=cfg.n_hits,
                             max_p=p, target_throughput=1e12)
        gt = parallelize(g0, req_t)
        model = gt.meta["parallelization"]["model_throughput_ev_s"]
        rows.append(row(f"p_sweep_P{p}", t / 128 * 1e6,
                        f"cpu {ev_s:,.0f} ev/s; tpu-model "
                        f"{model:,.0f} ev/s/chip"))
        p *= 4
    return rows


if __name__ == "__main__":
    run()
