"""The system under test, built as ``python -m repro.launch.serve``
builds it: the model's graph and requirements from ``serve.py``'s
``<model>_deployment(args, params)`` for the one model that the
configuration's ``--model`` names (default ``ccn``), through
``deploy()``, behind ``ShardedTriggerService``. Each pipeline is fed
the features its graph's ``input`` ops name.

A configuration's ``serve_args`` are ``serve.py`` flags and select the
path: the per-event executable (default), ``--buckets`` (one
batch-packed executable per occupancy bucket), or, with
``ragged_batch``, ``deploy(ragged=True, batch=ragged_batch)`` behind
``ShardedTriggerService(ragged=...)``. The benchmark supplies the
weights and the calibration feeds; nothing here is measured.
"""
from __future__ import annotations

import dataclasses
import time

import jax


class Timed:
    """Host clock and, when ``annotate``, a profiler span
    (``pipeline_call``) around every call into the served pipeline.
    ``wrap(fn)`` returns ``fn`` so timed; every wrapped function adds
    to the same counters. A call returns what the pipeline returns,
    unfinished. ``fault`` wraps each pipeline first (a test's planted
    fault)."""

    def __init__(self, annotate: bool, fault=None):
        self._annotate = annotate
        self._fault = fault
        self.calls = 0
        self.seconds = 0.0

    def wrap(self, fn):
        inner = fn if self._fault is None else self._fault(fn)

        def call(feeds):
            t = time.perf_counter()
            if self._annotate:
                with jax.profiler.TraceAnnotation("pipeline_call"):
                    out = inner(feeds)
            else:
                out = inner(feeds)
            self.seconds += time.perf_counter() - t
            self.calls += 1
            return out
        return call


class _Ragged:
    """The ragged pipeline as ``ShardedTriggerService(ragged=)`` takes
    it, with its calls timed."""

    def __init__(self, rp, timed: Timed):
        self.capacity = rp.capacity
        self.warmup = rp.warmup
        self._call = timed.wrap(rp)

    def __call__(self, feeds):
        return self._call(feeds)


@dataclasses.dataclass
class Served:
    service: object
    timed: Timed
    backend: str
    launch: dict          # {"b": events or bins per launch, "n": rows}


def _check_widths(cfg, graph, req):
    """The configuration file states the widths the program runs."""
    model = graph.meta["config"]
    for k, v in cfg["widths"].items():
        got = getattr(model, k)
        if got != v:
            raise ValueError(f"{cfg['name']}: widths.{k} is {v} in the "
                             f"configuration but the program runs {got}")
    if req.n_hits != cfg["detector"]["n_hits"]:
        raise ValueError(f"{cfg['name']}: n_hits {cfg['detector']['n_hits']}"
                         f" but the program deploys {req.n_hits}")


def deployment(args, params) -> dict:
    """``serve.py``'s deployment of the one model ``args.model``
    names: ``<model>_deployment(args, params)``."""
    from repro.launch import serve
    if len(args.model) != 1:
        raise ValueError(f"a cell serves one model; --model names "
                         f"{args.model}")
    fn = getattr(serve, f"{args.model[0]}_deployment", None)
    if fn is None:
        raise ValueError(f"serve.py has no {args.model[0]}_deployment")
    return fn(args, params)


def build(cfg: dict, params, calib: dict, *, annotate: bool,
          fault=None) -> Served:
    """Deploy the configuration with ``params`` and ``calib`` (the
    calibration feeds, stacked by feature), compile its launches, and
    start its service."""
    from repro.core.pipeline import deploy, deploy_bucketed
    from repro.launch import serve
    from repro.serving import ShardedTriggerService
    args = serve.build_parser().parse_args(cfg["serve_args"])
    m = deployment(args, params)
    graph, req = m["graph"], m["req"]
    _check_widths(cfg, graph, req)
    features = [op.attrs["feature"] for op in graph.inputs()]
    timed = Timed(annotate, fault)
    common = dict(n_replicas=args.replicas, window_s=2e-3,
                  policy=args.policy, loop=args.loop)
    rb = cfg.get("ragged_batch")
    if rb:
        rp = deploy(graph, req, batch=rb, ragged=True,
                    calibration_feeds=calib)
        svc = ShardedTriggerService(ragged=_Ragged(rp, timed),
                                    microbatch=16, **common)
        return Served(svc, timed, rp.backend,
                      {"b": rp.batch, "n": rp.capacity})
    if args.buckets:
        bp = deploy_bucketed(graph, req, buckets=args.buckets,
                             microbatch=args.bucket_microbatch,
                             calibration_feeds=calib)
        bp.warmup()
        svc = ShardedTriggerService(
            buckets={b: timed.wrap(fn) for b, fn in bp.infer_fns().items()},
            microbatch=args.bucket_microbatch, hedge_after_s=None, **common)
        return Served(svc, timed, bp.backend,
                      {"b": args.bucket_microbatch, "n": max(bp.buckets)})
    pipe = deploy(graph, req, calibration_feeds=calib)

    def infer(batch):
        return pipe({k: batch[k] for k in features})

    jax.block_until_ready(infer({k: v[:pipe.microbatch]
                                 for k, v in calib.items()}))
    svc = ShardedTriggerService(timed.wrap(infer),
                                microbatch=max(pipe.microbatch, 16),
                                hedge_after_s=None, **common)
    return Served(svc, timed, pipe.backend,
                  {"b": pipe.microbatch, "n": req.n_hits})
