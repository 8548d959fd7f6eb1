"""On-chip smoke test: the trigger server's main paths, once, on a TPU.

    python chip_smoke.py                # phases (a)-(d) on one chip
    python chip_smoke.py --four-chips   # four one-chip replicas vs one

Everything runs in this one process (a chip belongs to one process),
through the entry points a user calls: ``repro.launch.serve.main`` and
the ``deploy`` / ``ShardedTriggerService`` API. The CaloClusterNet
configuration is the paper's upgrade detector at full width (n_hits
128, d_hidden 64, d_s 4, d_flr 22, k 8) with random weights from seed
0 (``--train-steps 0``); events come from the seeded Belle II
generator.

(a) ``serve`` CCN, ``--precision mixed`` (calibrated int8 megakernel),
    per-event executable;
(b) ``serve --precision fp --buckets 32 64 128 --bucket-microbatch 8``,
    one batch-packed executable per occupancy bucket;
(c) ``deploy(ragged=True)`` behind ``ShardedTriggerService(ragged=...)``;
(d) ``serve --model gatedgcn ccn``, two routes behind one releaser.

(a)-(c) serve weights after a few seeded ``--train-steps`` (12 mixed,
10 fp), under which events fire, so decision equality tests
something. Every served pipeline must have the ``pallas`` kernel
backend and a compiled program that holds Mosaic kernels
(``tpu_custom_call``); the kernel count comes from the same
deployment built again through ``serve.ccn_deployment``. Release must
be exactly-once and in submission order. For (a)-(c) the served
outputs are compared, event by event, with that deployment built with
``kernel_backend="xla"`` (the jnp reference) on the same chip and run
at ``jax.default_matmul_precision("highest")``: trigger decisions
exactly, every per-hit head element within the f32 tolerance of
``tests/_numerics.py``. The same reference at the default precision,
which rounds f32 matmul inputs to bf16, is the control: it must miss
that tolerance, so a kernel that computed in bf16 would fail. Each phase prints one line with the device, the
backend, and the seconds JAX spent compiling (persistent-cache hits
included, and counted).

``--four-chips`` runs only ``serve --replicas 4`` on four chips and
the same traffic with ``--replicas 1``: each replica must have served
events with every output on its own device, release must be
exactly-once and in submission order, and the outputs must equal the
one-replica run's bit for bit.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
With no TPU, without the repository's sources beside this script, or
when any phase fails, the script exits non-zero and prints no such
line. JAX's compilation cache goes to ``JAX_COMPILATION_CACHE_DIR``
when it is set, else to ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
EXPECT_BACKEND = "pallas"
N_EVENTS = 256
# seeded condensation training under which events fire, so the
# decision check is not empty: the mixed path fires from 12 steps on
# (all events), the fp path at 10 (most, not all)
TRAIN_MIXED = ["--train-steps", "12"]
TRAIN_FP = ["--train-steps", "10"]


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


# ------------------------------------------------------------ metering ----
class CompileMeter:
    """Seconds JAX spends in backend compilation (a persistent-cache
    hit records its retrieval time instead) and the cache hits."""

    def __init__(self):
        import jax
        self.secs, self.compiles, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return (self.secs, self.compiles, self.hits)

    def since(self, mark):
        return {"compile_s": round(self.secs - mark[0], 3),
                "compiles": self.compiles - mark[1],
                "cache_hits": self.hits - mark[2]}


# ------------------------------------------------------------- helpers ----
def _stack(events, n=None):
    import numpy as np
    evs = events[:n] if n else events
    return {k: np.stack([e[k] for e in evs]) for k in evs[0]}


def count_kernels(pipe, feeds) -> int:
    """Mosaic kernels in the compiled launch of one micro-batch."""
    return pipe.lower(feeds).compile().as_text().count(KERNEL_MARK)


def _check_pallas(name, pipe, feeds) -> int:
    """The pipeline runs the Pallas backend, and its compiled launch of
    ``feeds`` (one micro-batch) holds Mosaic kernels; returns how
    many."""
    _check(pipe.backend == EXPECT_BACKEND,
           f"{name}: backend {pipe.backend!r}, expected "
           f"{EXPECT_BACKEND!r}")
    n = count_kernels(pipe, feeds)
    _check(n > 0, f"{name}: no tpu_custom_call in the compiled program")
    return n


def _check_served(name, s, n_events):
    """A ``serve.main`` summary: every event released once, none
    failed, submission order kept, Pallas kernels served."""
    _check(s["released"] == n_events and s["failed"] == 0,
           f"{name}: released {s['released']} of {n_events}, failed "
           f"{s['failed']}")
    _check(s["in_order"], f"{name}: release broke submission order")
    _check(s["backend"] == EXPECT_BACKEND,
           f"{name}: served backend {s['backend']!r}")


def _pad_rows(a, width):
    import numpy as np
    a = np.asarray(a)
    if a.shape[0] == width:
        return a
    pw = [(0, width - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, pw)


def references(ref, feeds):
    """The xla deployment ``ref`` on ``feeds`` at full f32 matmul
    precision, and at the default (bf16-input) precision as the
    control. One deployment serves both, so they share its calibrated
    int8 scales; only the jitted launches see the precision."""
    import jax
    lo = ref(feeds)
    with jax.default_matmul_precision("highest"):
        hi = ref(feeds)
    return hi, lo


def compare(name, served, hi, lo) -> dict:
    """Served per-event outputs and the bf16 control against the f32
    reference's batched ones: differing trigger decisions, and per-hit
    head elements beyond the f32 tolerance, counted for ``_judge``."""
    import numpy as np
    from _numerics import tolerance
    rtol, atol = tolerance("float32")
    got_t = np.array([bool(o["cps"]["trigger"]) for o in served])
    want_t = np.asarray(hi["cps"]["trigger"]).astype(bool)
    res = {"events": int(got_t.size), "triggers": int(want_t.sum()),
           "decisions_differ": int((got_t != want_t).sum())}
    sound = ctrl = 0.0
    over = ctrl_over = total = 0
    for head in sorted(k for k in hi if k != "cps"):
        w = np.asarray(hi[head], np.float64)
        g = np.stack([_pad_rows(o[head], w.shape[1])
                      for o in served]).astype(np.float64)
        c = np.asarray(lo[head], np.float64)
        _check(np.isfinite(g).all(), f"{name}/{head}: non-finite output")
        tol = atol + rtol * np.abs(w)
        sound = max(sound, float(np.max(np.abs(g - w))))
        ctrl = max(ctrl, float(np.max(np.abs(c - w))))
        over += int(np.sum(np.abs(g - w) > tol))
        ctrl_over += int(np.sum(np.abs(c - w) > tol))
        total += w.size
    res.update(max_diff=sound, beyond_f32_tol=over,
               bf16_control_diff=ctrl, control_beyond_f32_tol=ctrl_over,
               head_elements=total)
    return res


def _judge(name, res):
    _check(res["decisions_differ"] == 0,
           f"{name}: {res['decisions_differ']}/{res['events']} trigger "
           f"decisions differ from the f32 xla reference")
    _check(res["triggers"] > 0,
           f"{name}: no event fires; the decision check would be empty")
    _check(res["beyond_f32_tol"] == 0,
           f"{name}: {res['beyond_f32_tol']}/{res['head_elements']} head "
           f"elements beyond f32 tolerance of the reference (max "
           f"{res['max_diff']:.3e})")
    _check(res["control_beyond_f32_tol"] > 0,
           f"{name}: the bf16 control is within f32 tolerance; the check "
           f"would not catch a bf16 kernel")


def _line(phase, what, dev, backend, **fields):
    kv = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[smoke] phase {phase} {what}: device={dev['platform']} "
          f"kind={dev['kind']!r} backend={backend} {kv}", flush=True)


# -------------------------------------------------------------- phases ----
def phase_serve(serve, meter, dev, phase, argv, what):
    """(a)/(b): one ``serve.main`` run, then the same deployment built
    again for its kernel count and with the xla kernels as the
    reference, on the same events."""
    from repro.core.pipeline import _cut_hits, deploy, deploy_bucketed
    argv = argv + ["--events", str(N_EVENTS)]
    m0 = meter.mark()
    s = serve.main(argv)
    served_cost = meter.since(m0)
    _check_served(phase, s, N_EVENTS)
    args = serve.build_parser().parse_args(argv)
    m = serve.ccn_deployment(args, serve.ccn_params(args))
    events = m["events"](N_EVENTS, 7)        # serve.main's own events

    def build(**kw):
        if args.buckets:
            return deploy_bucketed(
                m["graph"], m["req"], buckets=args.buckets,
                microbatch=args.bucket_microbatch,
                calibration_feeds=m["calibration_feeds"], **kw)
        return deploy(m["graph"], m["req"],
                      calibration_feeds=m["calibration_feeds"], **kw)

    built = build()
    pipes = (built.pipes if args.buckets else {built.req.n_hits: built})
    kernels = sum(_check_pallas(
        f"{phase}/{b}", p, _cut_hits(_stack(events, p.microbatch), b))
        for b, p in pipes.items())
    m1 = meter.mark()
    res = compare(phase, s["outputs"], *references(build(
        kernel_backend="xla"), _stack(events)))
    _line(phase, what, dev, s["backend"], kernels=kernels,
          **served_cost, ref_compile_s=meter.since(m1)["compile_s"],
          **res)
    _judge(phase, res)


def phase_ragged(serve, meter, dev):
    """(c): the padding-free executable behind the ragged service."""
    from repro.core.pipeline import deploy
    from repro.serving import ShardedTriggerService
    args = serve.build_parser().parse_args(["--precision", "fp"]
                                           + TRAIN_FP)
    m = serve.ccn_deployment(args, serve.ccn_params(args))
    events = m["events"](N_EVENTS, 7)
    m0 = meter.mark()
    rp = deploy(m["graph"], m["req"], batch=8, ragged=True,
                calibration_feeds=m["calibration_feeds"])
    svc = ShardedTriggerService(ragged=rp, n_replicas=1, microbatch=16,
                                window_s=2e-3, loop="streaming")
    try:
        futs = [svc.submit(ev) for ev in events]
        served = [f.result(timeout=120) for f in futs]
    finally:
        svc.close()
    served_cost = meter.since(m0)
    bins = _stack(events, rp.batch)       # one launch: batch x 128 rows
    kernels = _check_pallas("c", rp.pipe, dict(
        bins, segids=bins["mask"].astype("int32"),
        slots=bins["mask"].astype("int32")))
    m1 = meter.mark()
    res = compare("c", served, *references(deploy(
        m["graph"], m["req"], batch=8, ragged=True,
        calibration_feeds=m["calibration_feeds"], kernel_backend="xla"),
        _stack(events)))
    _line("c", "deploy(ragged=True) + ShardedTriggerService(ragged=)",
          dev, rp.backend, kernels=kernels, **served_cost,
          ref_compile_s=meter.since(m1)["compile_s"], **res)
    _judge("c", res)


def phase_routes(serve, meter, dev):
    """(d): GatedGCN and CCN routes side by side."""
    argv = ["--model", "gatedgcn", "ccn", "--train-steps", "0",
            "--events", str(N_EVENTS // 2)]
    m0 = meter.mark()
    s = serve.main(argv)
    cost = meter.since(m0)
    _check_served("d", s, N_EVENTS // 2)
    for row in s["routes"]:
        _check(row["completed"] == row["submitted"] > 0,
               f"d: route {row['route']} completed {row['completed']} of "
               f"{row['submitted']}")
    args = serve.build_parser().parse_args(argv)
    kernels = 0
    for name in args.model:
        sv = serve.MODELS[name](args)
        kernels += _check_pallas(f"d/{name}", sv.pipe,
                                 _stack(sv.events(sv.pipe.microbatch, 0)))
    _line("d", "serve --model gatedgcn ccn", dev, s["backend"],
          kernels=kernels, **cost, events=s["released"],
          routes=",".join(f"{r['route']}:{r['completed']}"
                          for r in s["routes"]))


def phase_four_chips(serve, meter, dev):
    """``serve --replicas 4``, one replica per chip, against
    ``--replicas 1`` on the same traffic."""
    import jax
    import numpy as np
    devs = jax.devices()
    _check(len(devs) == 4, f"four chips expected, found {len(devs)}")
    argv = ["--precision", "mixed", "--train-steps", "0",
            "--events", str(N_EVENTS)]
    m0 = meter.mark()
    s4 = serve.main(argv + ["--replicas", "4"])
    cost = meter.since(m0)
    _check_served("four-chip", s4, N_EVENTS)
    rows = s4["per_replica"]
    _check([r["devices"] for r in rows] == [[str(d)] for d in devs],
           f"replica output devices {[r['devices'] for r in rows]}, "
           f"expected one each of {[str(d) for d in devs]}")
    _check(all(r["completed"] > 0 for r in rows)
           and sum(r["completed"] for r in rows) == N_EVENTS,
           f"per-replica completions {[r['completed'] for r in rows]}")
    s1 = serve.main(argv + ["--replicas", "1"])
    _check_served("one-replica", s1, N_EVENTS)
    diff = sum(not np.array_equal(x, y)
               for a, b in zip(s4["outputs"], s1["outputs"])
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))
    _check(diff == 0, f"{diff} output arrays differ from the one-replica "
                      f"run")
    print(f"[smoke] four-chip: device={dev['platform']} "
          f"kind={dev['kind']!r} count={len(devs)} backend={s4['backend']} "
          f"replicas=4 per_replica_events="
          f"{[r['completed'] for r in rows]} "
          f"output_devices={[r['devices'] for r in rows]} "
          f"exactly_once_in_order=True equal_to_one_replica=True "
          f"{' '.join(f'{k}={v}' for k, v in cost.items())}", flush=True)


ONE_CHIP_PHASES = {
    "a": lambda s, m, d: phase_serve(
        s, m, d, "a", ["--precision", "mixed"] + TRAIN_MIXED,
        "serve ccn upgrade --precision mixed (per-event)"),
    "b": lambda s, m, d: phase_serve(
        s, m, d, "b", ["--precision", "fp", "--buckets", "32", "64", "128",
                       "--bucket-microbatch", "8"] + TRAIN_FP,
        "serve ccn upgrade --precision fp --buckets 32 64 128"),
    "c": phase_ragged,
    "d": phase_routes,
}


# ---------------------------------------------------------------- main ----
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica service on four "
                         "chips and its one-replica comparison")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]
    try:
        from repro.launch import serve
    except ImportError as e:
        print(f"[smoke] FAIL: the repository's sources are not beside "
              f"this script ({e})", file=sys.stderr)
        return 2
    serve.configure_compile_cache()        # before the first compile
    import jax
    dev = serve.device_info()
    if dev["platform"] != "tpu":
        print(f"[smoke] FAIL: no TPU found (JAX's default device is "
              f"{dev['platform']} {dev['kind']!r}); nothing was run",
              file=sys.stderr)
        return 1
    meter = CompileMeter()
    phases = ({"four-chip": phase_four_chips} if args.four_chips
              else ONE_CHIP_PHASES)
    failed = []
    for name, fn in phases.items():
        try:
            fn(serve, meter, dev)
        except (Exception, SystemExit) as e:   # noqa: BLE001 — report,
            # then run the other phases: one call sees every fault
            failed.append(name)
            print(f"[smoke] phase {name} FAILED: "
                  f"{type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failed:
        print(f"[smoke] FAIL: phase(s) {', '.join(failed)} failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
