"""Serving driver: ``python -m repro.launch.serve [...]``.

Runs a registered model end to end on the default JAX device — a TPU
runs the compiled Pallas kernels, a CPU the jnp reference, and every
report line names the device's platform and ``device_kind``: deploy
it through the model-agnostic design flow at the chosen design point
(the model joins via its ``core.graph_ir`` exporter — serve.py has no
model-specific imports at module level), wrap the compiled pipeline in
the real-time sharded trigger service (micro-batching window, strict
in-order completion, hedged dispatch), stream synthetic events through
it, and report throughput/latency percentiles.

``main(argv)`` returns a summary (events released and failed, whether
release kept submission order, device, kernel backend, the per-event
outputs, and each replica's completed count and output devices) for
programmatic callers such as ``chip_smoke.py``. Without
``--inject-faults`` any failed event is an error: the run exits
non-zero, as it does when release breaks submission order. The
persistent compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``<repo>/.jax_cache``.

``--model`` picks the route(s) from the serve-side model registry
(``MODELS``; default ``ccn``). The single-model ``ccn`` selection runs
the paper's full demonstrator — brief condensation training, the
monitoring pipeline (paper §III-B: online ``MonitorSnapshot`` with
truth-matched efficiency/fake-rate, optional ``--monitor-port`` HTTP
endpoint, JSON event display), and the ``--buckets`` occupancy path.
Any other selection serves the named models side by side through
per-route replica groups (``ShardedTriggerService(routes=...)``) — the
CCN trigger next to the edge-based GNNs — and can write a
``--bench-out`` JSON with per-route serving stats.

``--buckets`` switches to the occupancy-bucketed path: one batch-packed
executable per n_hits tier (``deploy_bucketed``), each event dispatched
to the smallest bucket that fits its non-zero hit count, every bucket
pre-compiled before traffic — see docs/architecture.md.

Replicas run the persistent **streaming dataflow loop** by default
(rolling batching into preallocated rings, no deadline tick);
``--loop deadline`` is the escape hatch reproducing the original
micro-batch deadline loop exactly — see docs/serving.md.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import time
import urllib.request
from typing import Callable, NamedTuple

import jax
import numpy as np

from repro.core.graph_ir import export_graph
from repro.core.passes.parallelize import Requirements
from repro.core.pipeline import deploy, deploy_bucketed
from repro.serving import (FaultPlan, MonitorServer,
                           ShardedTriggerService, event_display,
                           write_display)

# the checkout root (src/repro/launch/serve.py -> repo)
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


# ------------------------------------------------------------ platform ----
def configure_compile_cache() -> str:
    """Persistent compilation cache for an entry point; call it before
    the first compile. When ``JAX_COMPILATION_CACHE_DIR`` is set JAX
    owns the cache and nothing is set here; otherwise the cache goes
    to ``<repo>/.jax_cache`` — a fixed path, because the path is part
    of what a later run must find again. Returns the directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every executable: the trigger's kernels each compile in
    # well under JAX's default one-second threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def device_info() -> dict:
    """The default device as JAX reports it."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _device_label() -> str:
    d = device_info()
    return d["platform"] if d["kind"] == d["platform"] \
        else f"{d['platform']} {d['kind']}"


def _us(x) -> str:
    """A latency in µs; undefined (None) when no event completed."""
    return "n/a" if x is None else f"{x:.0f}us"


def _requirements(args, **kw) -> Requirements:
    """Deployment requirements for the device this process runs on: a
    TPU deploys the Pallas kernels and costs the P-search with that
    chip's published peaks (``launch.mesh.chip_peaks``)."""
    d = device_info()
    return Requirements(design_point=args.design_point,
                        platform=d["platform"],
                        device_kind=d["kind"] if d["platform"] == "tpu"
                        else None,
                        target_throughput=args.target_throughput,
                        max_latency_s=2e-3, **kw)


# ------------------------------------------------------------ model zoo ----
class Servable(NamedTuple):
    """One deployed route: the compiled pipeline plus a synthetic
    per-event feed source matching its input features."""
    name: str
    pipe: Callable
    events: Callable      # (n, seed) -> list of per-event feed dicts


_EDGE_N, _EDGE_E = 64, 256     # E = 4N, the registry's edge budget


def _edge_events(d_in, d_edge_in=None):
    def events(n, seed):
        rng = np.random.default_rng(seed)
        out = []
        for _ in range(n):
            ev = {
                "nodes": rng.normal(
                    size=(_EDGE_N, d_in)).astype(np.float32),
                "edge_index": rng.integers(
                    0, _EDGE_N, size=(2, _EDGE_E)).astype(np.int32),
                "node_mask": (rng.uniform(size=(_EDGE_N,)) < 0.8)
                .astype(np.float32),
                "edge_mask": (rng.uniform(size=(_EDGE_E,)) < 0.7)
                .astype(np.float32),
            }
            if d_edge_in is not None:
                ev["edges"] = rng.normal(
                    size=(_EDGE_E, d_edge_in)).astype(np.float32)
            out.append(ev)
        return out
    return events


def _ccn_configs(detector: str):
    from repro.core import caloclusternet as ccn
    from repro.data.belle2 import Belle2Config, current_detector
    if detector == "current":
        return ccn.current_detector_config(), current_detector()
    return ccn.CCNConfig(), Belle2Config()


def ccn_params(args):
    """The CCN route's weights: random init from seed 0, then
    ``--train-steps`` steps of condensation training (AdamW, seeded
    batches) so the demo's decisions are meaningful."""
    from repro.core import caloclusternet as ccn
    from repro.data.belle2 import generate
    cfg, gen_cfg = _ccn_configs(args.detector)
    params = ccn.init(jax.random.PRNGKey(0), cfg)
    if args.train_steps <= 0:
        return params
    import jax.numpy as jnp

    from repro.core.condensation import condensation_loss
    from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                             cosine_warmup)
    ocfg = AdamWConfig(weight_decay=0.01)
    lrf = cosine_warmup(peak_lr=2e-3, warmup_steps=10,
                        total_steps=args.train_steps)
    opt = adamw_init(params, ocfg)

    @jax.jit
    def _step(p, o, b):
        def lf(q):
            out = ccn.apply(q, b["feats"], b["mask"], cfg)
            labels = {"object_id": b["object_id"],
                      "energy": b["energy"], "cls": b["cls"]}
            return condensation_loss(out, labels, b["mask"],
                                     k_max=cfg.k_max)
        (l, m), g = jax.value_and_grad(lf, has_aux=True)(p)
        p2, o2, _ = adamw_update(g, o, p, lr=lrf(o["step"]), cfg=ocfg)
        return p2, o2, l

    for st in range(args.train_steps):
        raw = generate(gen_cfg, 32, seed=500 + st)
        b = {k: jnp.asarray(v) for k, v in raw.items()
             if k != "trigger_truth"}
        params, opt, l = _step(params, opt, b)
    print(f"[serve] warm-trained {args.train_steps} steps, "
          f"loss {float(l):.3f}")
    return params


def ccn_deployment(args, params=None) -> dict:
    """What the CCN route deploys: the model graph (random init from
    seed 0 unless ``params`` are given, e.g. ``ccn_params(args)``), its
    Requirements on this device, the calibration feeds, and a
    synthetic per-event feed source ``events(n, seed)``."""
    from repro.core import caloclusternet as ccn
    from repro.data.belle2 import generate
    cfg, gen_cfg = _ccn_configs(args.detector)
    if params is None:
        params = ccn.init(jax.random.PRNGKey(0), cfg)
    calib = generate(gen_cfg, 64, seed=123)

    def events(n, seed):
        ev = generate(gen_cfg, n, seed=seed)
        return [{"hits": ev["feats"][i], "mask": ev["mask"][i]}
                for i in range(n)]

    return {"graph": export_graph("caloclusternet", params, cfg),
            "req": _requirements(
                args, precision_policy=args.precision, n_hits=cfg.n_hits,
                tpu_native_gravnet=args.tpu_native_gravnet),
            "calibration_feeds": {"hits": calib["feats"],
                                  "mask": calib["mask"]},
            "events": events}


def _ccn_servable(args) -> Servable:
    m = ccn_deployment(args)
    return Servable("ccn", deploy(m["graph"], m["req"],
                                  calibration_feeds=m["calibration_feeds"]),
                    m["events"])


def _gatedgcn_servable(args) -> Servable:
    from repro.models.gnn import gatedgcn
    cfg = gatedgcn.GatedGCNConfig(n_layers=4, d_hidden=32, d_in=8,
                                  d_edge_in=4, n_classes=2)
    params = gatedgcn.init(jax.random.PRNGKey(1), cfg)
    graph = export_graph("gatedgcn", params, cfg)
    req = _requirements(args, precision_policy="fp", n_hits=_EDGE_N)
    return Servable("gatedgcn", deploy(graph, req),
                    _edge_events(cfg.d_in, cfg.d_edge_in))


def _graphsage_servable(args) -> Servable:
    from repro.models.gnn import graphsage
    cfg = graphsage.GraphSAGEConfig(n_layers=2, d_hidden=32, d_in=16,
                                    n_classes=5)
    params = graphsage.init(jax.random.PRNGKey(2), cfg)
    graph = export_graph("graphsage", params, cfg)
    req = _requirements(args, precision_policy="fp", n_hits=_EDGE_N)
    return Servable("graphsage", deploy(graph, req),
                    _edge_events(cfg.d_in))


MODELS: dict[str, Callable] = {
    "ccn": _ccn_servable,
    "gatedgcn": _gatedgcn_servable,
    "graphsage": _graphsage_servable,
}


def _tune_and_rebind(cache, args, problems, redeploy):
    """Autotune the given (graph, n_rows, batch, backend) problems,
    persist winners, and redeploy with them bound; returns the fresh
    deployment or None when nothing new was searched."""
    from repro.tuning import autotune_graph
    n_new = sum(autotune_graph(g, n_rows=nr, batch=bt, backend=be,
                               cache=cache, verbose=True)
                for g, nr, bt, be in problems)
    print(f"[serve] autotuned {n_new} kernel problem(s), "
          f"cache holds {len(cache)}")
    if args.tuning_cache:
        cache.save(args.tuning_cache)
        print(f"[serve] tuning cache -> {args.tuning_cache}")
    return redeploy() if n_new else None   # rebind fresh winners


def _fault_kwargs(args) -> dict:
    """Fault-tolerance service kwargs from the CLI: a seeded fault
    plan (--inject-faults implies the breaker — injecting chaos
    without health tracking just loses events), circuit breaking,
    bounded failover, and load shedding."""
    faults = FaultPlan.parse(args.inject_faults, seed=args.fault_seed) \
        if args.inject_faults else None
    if faults is not None:
        print(f"[serve] chaos plan: {faults.describe()}")
    return {"faults": faults,
            "breaker": args.breaker or faults is not None,
            "max_retries": args.max_retries,
            "shed": args.shed}


def _print_chaos(eng, failed: int):
    ft = eng.fault_tolerance_summary()
    br = ft["breaker"]
    print(f"[serve] chaos: {failed} client-visible failure(s), "
          f"shed={ft['shed']} retried={ft['retried']} "
          f"failed_over={ft['failed_over']} "
          f"breaker open={br['open']} half_open={br['half_open']}")


def _serve_multimodel(args):
    """Heterogeneous-model serving: one route (replica group) per
    requested model behind a single global in-order release stage."""
    servables = [MODELS[m](args) for m in args.model]
    mb = max(8, *(getattr(s.pipe, "microbatch", 1) for s in servables))
    for s in servables:   # warm up compile before traffic
        warm = s.events(mb, 99)
        s.pipe({k: np.stack([e[k] for e in warm]) for k in warm[0]})
    print(f"[serve] deployed design ③{args.design_point} routes="
          f"{[s.name for s in servables]} microbatch={mb}")
    fk = _fault_kwargs(args)
    eng = ShardedTriggerService(
        routes={s.name: s.pipe for s in servables},
        n_replicas=args.replicas, microbatch=mb, window_s=2e-3,
        policy=args.policy, loop=args.loop, **fk)
    per = {s.name: s.events(args.events // len(servables) +
                            (i < args.events % len(servables)),
                            seed=7 + i)
           for i, s in enumerate(servables)}
    t0 = time.perf_counter()
    futs = []
    cursors = {name: iter(evs) for name, evs in per.items()}
    live = list(cursors)
    while live:               # interleave the model streams
        for name in list(live):
            ev = next(cursors[name], None)
            if ev is None:
                live.remove(name)
            else:
                futs.append(eng.submit(ev, route=name))
    results, failed, order = _collect(futs)
    dt = time.perf_counter() - t0
    eng.drain()
    in_order = order == list(range(len(futs)))
    released = len(results)
    s = eng.stats.summary()
    print(f"[serve] {released} events in {dt:.2f}s -> "
          f"{released / dt:,.0f} ev/s ({_device_label()}, "
          f"{args.replicas} replica(s) per route, {args.policy}, "
          f"{args.loop} loop)")
    print(f"[serve] latency p50={_us(s['p50_us'])} "
          f"p99={_us(s['p99_us'])} batches={s['batches']}")
    route_rows = eng.route_summary()
    for row in route_rows:
        print(f"[serve]   route {row['route']}: "
              f"{row['submitted']} submitted, {row['completed']} "
              f"completed, {row['batches']} batches")
    if fk["faults"] is not None:
        _print_chaos(eng, failed)
    eng.close()
    if args.bench_out:
        bench = {
            "events": args.events, "elapsed_s": dt, "loop": args.loop,
            "throughput_ev_s": released / dt,
            "p50_us": s["p50_us"], "p99_us": s["p99_us"],
            "routes": {row["route"]: {k: v for k, v in row.items()
                                      if k != "route"}
                       for row in route_rows},
            "released_nonzero": released > 0,
        }
        with open(args.bench_out, "w") as f:
            json.dump(bench, f, indent=2)
        print(f"[serve] multi-model stats -> {args.bench_out}")
    if released < args.events:
        raise SystemExit("multi-model serving released fewer events "
                         "than were submitted")
    _fail_unless_sound(fk, failed, in_order)
    if fk["faults"] is None and any(
            row["completed"] != row["submitted"] for row in route_rows):
        raise SystemExit("multi-model serving released fewer events "
                         "than were submitted")
    return _summary([sv.pipe for sv in servables], results, failed,
                    in_order, s, routes=route_rows)


def _collect(futs):
    """Wait for every future in submission order; a failed event is
    kept as ``None`` so results stay aligned with submissions. Also
    returns the release order: each future's done-callback appends its
    index, and callbacks attached in index order run in release order
    (one attached to a future already done runs at once, after every
    earlier release). Read it after ``drain()``, which returns once
    the last release, callbacks included, is over."""
    order = []
    for i, f in enumerate(futs):
        f.add_done_callback(lambda _f, i=i: order.append(i))
    results, failed = [], 0
    for f in futs:
        try:
            results.append(f.result(timeout=120))
        except Exception:  # noqa: BLE001 — counted, judged by caller
            results.append(None)
            failed += 1
    return results, failed, order


def _fail_unless_sound(fk, failed: int, in_order: bool):
    """Without a fault plan a failed event is a broken deployment,
    not a statistic, and release out of submission order is broken
    with or without one: exit non-zero."""
    if fk["faults"] is None and failed:
        raise SystemExit(f"{failed} event(s) failed without injected "
                         "faults")
    if not in_order:
        raise SystemExit("events were released out of submission order")


def _summary(pipes, results, failed, in_order, stats, **extra) -> dict:
    return {"released": len(results), "failed": failed,
            "in_order": in_order, "device": device_info(),
            "backend": ",".join(sorted({p.backend for p in pipes})),
            "outputs": results,
            "per_replica": [{k: r[k] for k in ("replica_id", "completed",
                                               "devices")}
                            for r in stats["per_replica"]],
            **extra}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", nargs="+", default=["ccn"],
                    choices=sorted(MODELS), metavar="NAME",
                    help="registered model route(s) to serve (default "
                         "ccn). A single 'ccn' runs the full "
                         "demonstrator (training, buckets, "
                         "monitoring); any other selection serves the "
                         "named models side by side through per-route "
                         "replica groups")
    ap.add_argument("--bench-out", default=None, metavar="PATH",
                    help="write per-route serving stats JSON "
                         "(multi-model path only)")
    ap.add_argument("--detector", choices=["current", "upgrade"],
                    default="upgrade")
    ap.add_argument("--design-point", type=int, default=3,
                    choices=[1, 2, 3])
    ap.add_argument("--precision", choices=["fp", "mixed"],
                    default="mixed")
    ap.add_argument("--events", type=int, default=512)
    ap.add_argument("--target-throughput", type=float, default=1e5,
                    help="events/s target for the P-search, costed "
                         "with the device's peaks")
    ap.add_argument("--tpu-native-gravnet", action="store_true")
    ap.add_argument("--train-steps", type=int, default=40)
    ap.add_argument("--event-display", default=None, metavar="PATH",
                    help="write a JSON event display (shared "
                         "event_display() records, detector-correct "
                         "grid) for the first --event-display-n events")
    ap.add_argument("--event-display-n", type=int, default=16,
                    metavar="N", help="events in the --event-display "
                                      "file (default 16)")
    ap.add_argument("--monitor-port", type=int, default=None,
                    metavar="PORT",
                    help="serve the live monitor over HTTP on this "
                         "port (0 = ephemeral): /snapshot JSON, "
                         "/events NDJSON tail, / HTML/SVG display")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serving replicas (thread-backed on one "
                         "device, device-placed when several exist)")
    ap.add_argument("--loop", choices=["streaming", "deadline"],
                    default="streaming",
                    help="replica hot loop: 'streaming' (default) runs "
                         "the persistent dataflow pipeline — rolling "
                         "batching into preallocated rings, no "
                         "deadline tick; 'deadline' is the escape "
                         "hatch reproducing the original micro-batch "
                         "deadline loop exactly")
    ap.add_argument("--policy", default="round_robin",
                    choices=["round_robin", "least_loaded"])
    ap.add_argument("--inject-faults", default=None, metavar="SPEC",
                    help="deterministic chaos: seeded fault plan, e.g. "
                         "'fail:p=0.05;stall:p=0.02,s=0.01' or "
                         "'fail:p=1.0,replica=1' (dead lane); grammar "
                         "in docs/serving.md. Implies --breaker")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for --inject-faults (bit-identical "
                         "replay)")
    ap.add_argument("--breaker", action="store_true",
                    help="per-replica health tracking + circuit "
                         "breaking (closed/open/half-open)")
    ap.add_argument("--max-retries", type=int, default=0, metavar="N",
                    help="failover: re-dispatch a failed batch's "
                         "events to a healthy sibling up to N times "
                         "before failing to the client")
    ap.add_argument("--shed", action="store_true",
                    help="load shedding: a full replica queue fails "
                         "the event fast with ShedError instead of "
                         "blocking submit()")
    ap.add_argument("--buckets", type=int, nargs="+", default=None,
                    metavar="N_HITS",
                    help="occupancy buckets (e.g. 8 16 32): deploy one "
                         "batch-packed executable per bucket and "
                         "dispatch each event to the smallest bucket "
                         "that fits its non-zero hit count")
    ap.add_argument("--bucket-microbatch", type=int, default=8,
                    metavar="B",
                    help="micro-batch width each bucket executable "
                         "packs per launch (default 8)")
    ap.add_argument("--tuning-cache", default=None, metavar="PATH",
                    help="JSON kernel-tuning cache consulted when "
                         "binding kernels and warming replicas "
                         "(absent/corrupt -> heuristic defaults)")
    ap.add_argument("--tune", action="store_true",
                    help="autotune this deployment's kernel shapes "
                         "before serving; winners are persisted to "
                         "--tuning-cache when given")
    ap.add_argument("--no-fuse-gravnet-block", action="store_true",
                    help="escape hatch: keep the unfused dense→"
                         "aggregate→dense GravNet chains (legacy "
                         "graphs and tuning-cache keys, bit-for-bit) "
                         "instead of the fused megakernel")
    ap.add_argument("--no-fuse-int8", action="store_true",
                    help="int8-specific escape hatch: under "
                         "--precision mixed, keep the legacy unfused "
                         "calibrated int8 dense chain (and its tuning "
                         "keys, bit-for-bit) instead of the quantized "
                         "megakernel; fp deployments still fuse")
    return ap


def main(argv=None) -> dict:
    """Serve once and return the run's summary (see the module
    docstring); ``argv`` defaults to the command line."""
    args = build_parser().parse_args(argv)
    configure_compile_cache()

    if args.model != ["ccn"]:
        return _serve_multimodel(args)

    from repro.data.belle2 import generate
    cfg, gen_cfg = _ccn_configs(args.detector)
    model = ccn_deployment(args, ccn_params(args))
    graph, req = model["graph"], model["req"]
    feeds = model["calibration_feeds"]
    cache = None
    if args.tuning_cache or args.tune:
        from repro.tuning import TuningCache
        cache = TuningCache.load(args.tuning_cache) if args.tuning_cache \
            else TuningCache()
        if cache.load_error:
            print(f"[serve] WARNING: {cache.load_error}; "
                  "falling back to heuristic kernel defaults")
    monitoring = args.monitor_port is not None or args.event_display
    monitor_cfg = {"detector": gen_cfg,
                   "display_n": max(args.event_display_n, 64)} \
        if monitoring else False
    fuse_block = not args.no_fuse_gravnet_block
    fuse_int8 = not args.no_fuse_int8
    fk = _fault_kwargs(args)
    if args.buckets:
        mb = args.bucket_microbatch

        def redeploy(**kw):
            return deploy_bucketed(
                graph, req, buckets=args.buckets, microbatch=mb,
                calibration_feeds=feeds, tuning_cache=cache,
                fuse_gravnet_block=fuse_block, fuse_int8=fuse_int8, **kw)

        bpipe = redeploy()
        if args.tune:
            fresh = _tune_and_rebind(
                cache, args,
                [(p.graph, b, mb, p.backend)
                 for b, p in bpipe.pipes.items()], redeploy)
            if fresh is not None:
                bpipe = fresh
        served = bpipe
        print(f"[serve] deployed design ③{args.design_point} "
              f"buckets={bpipe.buckets} microbatch={mb} "
              f"(one batch-packed executable per bucket)")
        eng = ShardedTriggerService(
            buckets=bpipe, n_replicas=args.replicas, microbatch=mb,
            window_s=2e-3, hedge_after_s=None, policy=args.policy,
            monitor=monitor_cfg, loop=args.loop, **fk)
        print(f"[serve] bucket executables pre-compiled at startup: "
              f"{sum(r.warmed for r in eng.replicas)}")
    else:
        def redeploy(**kw):
            return deploy(graph, req, calibration_feeds=feeds,
                          tuning_cache=cache,
                          fuse_gravnet_block=fuse_block,
                          fuse_int8=fuse_int8, **kw)

        pipe = redeploy()
        if args.tune:
            fresh = _tune_and_rebind(
                cache, args, [(pipe.graph, cfg.n_hits, 1, pipe.backend)],
                redeploy)
            if fresh is not None:
                pipe = fresh
        served = pipe
        print(f"[serve] deployed design ③{args.design_point} "
              f"segments={len(pipe.segments)} P={pipe.par}")

        def infer(batch):
            return pipe({"hits": batch["hits"], "mask": batch["mask"]})

        # warmup compile
        warm = {k: v[:pipe.microbatch] for k, v in feeds.items()}
        infer(warm)

        warmup_fn = None
        if cache is not None and len(cache):
            from repro.tuning import make_warmup
            warmup_fn = make_warmup(cache, backend=pipe.backend)
        eng = ShardedTriggerService(
            infer, n_replicas=args.replicas,
            microbatch=max(pipe.microbatch, 16), window_s=2e-3,
            hedge_after_s=None, policy=args.policy, warmup_fn=warmup_fn,
            monitor=monitor_cfg, loop=args.loop, **fk)
        if warmup_fn is not None:
            print(f"[serve] replicas warmed "
                  f"{sum(r.warmed for r in eng.replicas)} cached kernel "
                  f"shape(s) at startup")
    server = None
    if args.monitor_port is not None:
        server = MonitorServer.for_service(eng, port=args.monitor_port)
        print(f"[serve] monitor live at {server.url} "
              f"(/snapshot, /events, / = event display)")
    events = generate(gen_cfg, args.events, seed=7)
    truth = events["trigger_truth"] > 0
    t0 = time.perf_counter()
    futs = []
    for i in range(args.events):
        futs.append(eng.submit({"hits": events["feats"][i],
                                "mask": events["mask"][i]},
                               truth=bool(truth[i]) if monitoring
                               else None))
    results, failed, order = _collect(futs)
    dt = time.perf_counter() - t0
    eng.drain()
    in_order = order == list(range(len(futs)))
    s = eng.stats.summary()
    trig = np.asarray([bool(r["cps"]["trigger"]) if r is not None
                       else False for r in results])
    eff = float((trig & truth).sum() / max(truth.sum(), 1))
    fake = float((trig & ~truth).sum() / max((~truth).sum(), 1))
    print(f"[serve] {args.events} events in {dt:.2f}s -> "
          f"{args.events / dt:,.0f} ev/s ({_device_label()}, "
          f"{args.replicas} replica(s), {args.policy}, "
          f"{args.loop} loop)")
    print(f"[serve] latency p50={_us(s['p50_us'])} "
          f"p99={_us(s['p99_us'])} batches={s['batches']}")
    bud = s["budget"]
    print(f"[serve] budget queue_wait={_us(bud['queue_wait_us_mean'])} "
          f"dispatch={_us(bud['dispatch_us_mean'])} "
          f"compute={_us(bud['compute_us_mean'])}")
    for rs in s["per_replica"]:
        print(f"[serve]   replica {rs['replica_id']}: "
              f"{rs['completed']} events, {rs['batches']} batches, "
              f"{rs['throughput_ev_s']:,.0f} ev/s")
    if args.buckets:
        for bs in eng.bucket_summary():
            print(f"[serve]   bucket n_hits<={bs['bucket']}: "
                  f"{bs['submitted']} events, {bs['batches']} batches, "
                  f"{bs['padded_events']} padded")
    print(f"[serve] trigger efficiency={eff:.3f} fake rate={fake:.3f} "
          f"in-order={in_order}")
    if fk["faults"] is not None:
        _print_chaos(eng, failed)
    if monitoring:
        snap = eng.monitor_snapshot()

        def f3(x):      # snapshot stats are None when undefined (e.g.
            return "n/a" if x is None else f"{x:.3f}"   # one-class truth)

        print(f"[serve] monitor: {snap['events']} events, "
              f"trigger_rate={f3(snap['trigger_rate'])}, "
              f"efficiency={f3(snap['efficiency'])}, "
              f"fake_rate={f3(snap['fake_rate'])}, "
              f"rate={snap['rate_ev_s']:,.0f} ev/s (windowed)")
    if server is not None:
        # prove the live endpoint agrees with the engine's own stats
        live = json.load(urllib.request.urlopen(
            f"{server.url}/snapshot", timeout=10))
        ok = live["events"] == s["completed"]
        print(f"[serve] /snapshot events={live['events']} vs "
              f"stats completed={s['completed']} -> "
              f"{'MATCH' if ok else 'MISMATCH'}")
        if not ok:
            raise SystemExit("monitor snapshot disagrees with "
                             "serving stats")
    if args.event_display:
        disp = [event_display(r["cps"], event_id=i, detector=gen_cfg,
                              truth=bool(truth[i]))
                for i, r in enumerate(results[:args.event_display_n])
                if r is not None]
        write_display(args.event_display, disp)
        print(f"[serve] event display ({len(disp)} events) -> "
              f"{args.event_display}")
    if server is not None:
        server.close()
    eng.close()
    _fail_unless_sound(fk, failed, in_order)
    return _summary([served], results, failed, in_order, s)


if __name__ == "__main__":
    main()
