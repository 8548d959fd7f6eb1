"""Serving scaling sweep: replica count x micro-batch size.

Drives the sharded trigger service over a grid of (replicas,
microbatch) operating points and emits a JSON trajectory with
aggregate and per-replica throughput/latency-budget stats — the
scaling analogue of the paper's Fig. 5 throughput curves.

Two inference backends:

  synthetic (default) — a fixed-service-time model of an accelerator
      lane (``--service-us`` per batch, GIL-free wait + a small numpy
      trigger computation).  Replica scaling is then governed purely by
      the serving layer, so aggregate throughput must grow
      monotonically with replica count at fixed micro-batch — the
      acceptance check this benchmark enforces with ``--check``.
  pipeline — a real ``deploy()``-produced CaloClusterNet executable
      shared by all (virtual) replicas; useful for profiling the
      serving layer against actual compute, but thread scaling then
      depends on how much the backend releases the GIL.

A third mode measures **batch packing** (the occupancy-bucketed
serving path): ``--mode batching`` streams current-detector events
through the service once with ``microbatch=1`` against the per-event
executable (the pre-batching baseline: one launch per event) and once
per requested micro-batch against the matching batch-packed
executable (``deploy(batch=mb)``, one launch per micro-batch), and
records the events/s speedup in the JSON's ``batching`` section.
``--check`` then requires ≥1.5× for every micro-batch ≥ 8.

Usage:
    PYTHONPATH=src python benchmarks/serving_scaling.py \
        --out /tmp/serving_scaling.json --check
    PYTHONPATH=src python benchmarks/serving_scaling.py \
        --mode batching --out /tmp/serving_batching.json --check
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.serving import ShardedTriggerService


# ----------------------------------------------------------- inference ----
def synthetic_infer(service_us: float):
    """Fixed-service-time lane: sleep models the accelerator occupancy
    (releases the GIL, like a real device dispatch), then a trivial
    numpy trigger decision so the result shape is event-like."""

    def infer(feeds):
        time.sleep(service_us * 1e-6)
        x = feeds["hits"]
        energy = x.sum(axis=tuple(range(1, x.ndim)))
        return {"trigger": energy > 0.0, "energy": energy}

    return infer


def pipeline_infer(batch: int = 1):
    """Current-detector CaloClusterNet executable; ``batch > 1``
    deploys the batch-packed form (one launch per micro-batch)."""
    import jax

    from repro.core import caloclusternet as ccn
    from repro.core.passes.parallelize import Requirements
    from repro.core.pipeline import deploy
    from repro.data.belle2 import Belle2Config, generate

    cfg = ccn.CCNConfig(n_hits=32, n_crystals=576)
    gen = Belle2Config(n_crystals=576, grid=(24, 24), n_hits=32,
                       noise_rate=8.0)
    params = ccn.init(jax.random.PRNGKey(0), cfg)
    graph = ccn.to_graph(params, cfg)
    calib = generate(gen, 32, seed=1)
    req = Requirements(design_point=3, platform="cpu",
                       precision_policy="mixed", n_hits=cfg.n_hits,
                       target_throughput=2e4, max_latency_s=2e-3)
    pipe = deploy(graph, req, calibration_feeds={
        "hits": calib["feats"], "mask": calib["mask"]}, batch=batch)

    def infer(feeds):
        return pipe({"hits": feeds["hits"], "mask": feeds["mask"]})

    def make_event(rng):
        i = rng.integers(0, 32)
        return {"hits": calib["feats"][i], "mask": calib["mask"][i]}

    return infer, make_event


# --------------------------------------------------------------- sweep ----
def run_point(infer, make_event, *, replicas, microbatch, events,
              window_s, policy):
    rng = np.random.default_rng(0)
    evs = [make_event(rng) for _ in range(events)]
    # construct after event generation so the stats clocks (which back
    # aggregate/per-replica throughput_ev_s) start at streaming time
    svc = ShardedTriggerService(infer, n_replicas=replicas,
                                microbatch=microbatch, window_s=window_s,
                                policy=policy, devices="auto")
    t0 = time.perf_counter()
    futs = [svc.submit(e) for e in evs]
    for f in futs:
        f.result(timeout=300)
    wall = time.perf_counter() - t0
    svc.drain()
    summary = svc.stats.summary()
    svc.close()
    return {
        "replicas": replicas,
        "microbatch": microbatch,
        "events": events,
        "wall_s": wall,
        "throughput_ev_s": events / wall,
        "aggregate": summary,
    }


# ------------------------------------------------------------ batching ----
def run_batching(args):
    """Per-event baseline vs batch-packed micro-batches through the
    real serving stack on the current-detector config."""
    mbs = sorted(mb for mb in args.microbatches if mb > 1)
    points = []
    for mb in [1] + mbs:
        infer, make_event = pipeline_infer(batch=mb)
        # warm the compile cache so the measurement is steady-state
        e = make_event(np.random.default_rng(0))
        infer({k: np.stack([v] * mb) for k, v in e.items()})
        pt = run_point(infer, make_event, replicas=1, microbatch=mb,
                       events=args.events,
                       window_s=args.window_ms * 1e-3, policy=args.policy)
        points.append(pt)
    base = points[0]["throughput_ev_s"]
    section = []
    print("microbatch,throughput_ev_s,speedup_vs_per_event")
    for pt in points:
        speedup = pt["throughput_ev_s"] / base
        section.append({
            "microbatch": pt["microbatch"],
            "events": pt["events"],
            "throughput_ev_s": pt["throughput_ev_s"],
            "per_event_ev_s": base,
            "speedup_vs_per_event": speedup,
            "aggregate": pt["aggregate"],
        })
        print(f"{pt['microbatch']},{pt['throughput_ev_s']:.0f},"
              f"{speedup:.2f}")
    return section


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["synthetic", "pipeline", "batching"],
                    default="synthetic")
    ap.add_argument("--replicas", type=int, nargs="+", default=[1, 2, 4])
    ap.add_argument("--microbatches", type=int, nargs="+",
                    default=[8, 16, 32])
    ap.add_argument("--events", type=int, default=1024)
    ap.add_argument("--service-us", type=float, default=20000.0,
                    help="synthetic per-batch service time; keep it "
                         "large enough that lane capacity (not host "
                         "python overhead) is the binding constraint")
    ap.add_argument("--window-ms", type=float, default=50.0)
    ap.add_argument("--policy", default="round_robin",
                    choices=["round_robin", "least_loaded"])
    ap.add_argument("--out", default="/tmp/serving_scaling.json")
    ap.add_argument("--check", action="store_true",
                    help="fail unless aggregate throughput is monotone "
                         "in replica count at every micro-batch size")
    args = ap.parse_args()

    if args.mode == "batching":
        section = run_batching(args)
        result = {"mode": "batching", "detector": "current",
                  "events": args.events, "batching": section}
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"[serving_scaling] wrote {args.out}")
        if args.check:
            bad = [p for p in section
                   if p["microbatch"] >= 8
                   and p["speedup_vs_per_event"] < 1.5]
            for p in section:
                print(f"[serving_scaling] batching mb={p['microbatch']} "
                      f"{p['throughput_ev_s']:.0f} ev/s "
                      f"({p['speedup_vs_per_event']:.2f}x per-event)")
            if bad:
                raise SystemExit(
                    "serving_scaling: batch packing under 1.5x vs the "
                    f"per-event baseline at {[p['microbatch'] for p in bad]}")
        return

    if args.mode == "synthetic":
        infer = synthetic_infer(args.service_us)

        def make_event(rng):
            return {"hits": rng.normal(size=(32, 4)).astype(np.float32)}
    else:
        infer, make_event = pipeline_infer()
        # warm the compile cache for every micro-batch shape up front
        for mb in args.microbatches:
            e = make_event(np.random.default_rng(0))
            infer({k: np.stack([v] * mb) for k, v in e.items()})

    print("replicas,microbatch,events,wall_s,throughput_ev_s,"
          "p99_us,queue_wait_us,pack_us,pipeline_us,release_us")
    trajectory = []
    for mb in args.microbatches:
        for r in args.replicas:
            pt = run_point(infer, make_event, replicas=r, microbatch=mb,
                           events=args.events,
                           window_s=args.window_ms * 1e-3,
                           policy=args.policy)
            trajectory.append(pt)
            agg = pt["aggregate"]
            bud = agg["budget"]
            print(f"{r},{mb},{pt['events']},{pt['wall_s']:.3f},"
                  f"{pt['throughput_ev_s']:.0f},{agg['p99_us']:.0f},"
                  f"{bud['queue_wait_us_mean']:.0f},"
                  f"{bud['pack_us_mean']:.0f},"
                  f"{bud['pipeline_us_mean']:.0f},"
                  f"{bud['release_us_mean']:.0f}")

    result = {"mode": args.mode, "events": args.events,
              "service_us": args.service_us if args.mode == "synthetic"
              else None,
              "policy": args.policy, "trajectory": trajectory}
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"[serving_scaling] wrote {args.out}")

    if args.check:
        ok = True
        for mb in args.microbatches:
            pts = sorted((p for p in trajectory if p["microbatch"] == mb),
                         key=lambda p: p["replicas"])
            tps = [p["throughput_ev_s"] for p in pts]
            mono = all(b >= a for a, b in zip(tps, tps[1:]))
            print(f"[serving_scaling] mb={mb} throughput "
                  f"{[f'{t:.0f}' for t in tps]} monotone={mono}")
            ok &= mono
        if not ok:
            raise SystemExit("serving_scaling: throughput not monotone "
                             "in replica count")


if __name__ == "__main__":
    main()
