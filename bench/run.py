"""One run of one benchmark cell on the chip.

    python bench/run.py --workload <config>.<traffic> --seed N \
        --seconds S --trace 0|1

A cell is a workload of ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``). Everything that belongs to one
model comes from the plain reference the configuration names
(``bench/references/<reference>.py``): its ``pool`` makes the event
pool and the calibration events from ``--seed`` and the detector's
knobs, its ``weights`` the weights, its ``model_flops`` the operations
of one event. The run builds the served model as ``python -m
repro.launch.serve`` builds it (``sut.py``, through the deployment
that ``serve.py`` gives the model named by the configuration's
``--model``), warms up every launch shape through the service, and
then offers the mix for ``--seconds``: ``backlog`` keeps the service's
bounded queue full; ``poisson`` submits open-loop at the mix's fixed
rate. Every latency is timed from an event's scheduled arrival to the
resolution of its future, which comes after the in-order release
stage.

After the window every event submitted in it is awaited, the
service is closed, and a sample of the released events, drawn from
the seed, is compared with the reference: see ``check.py``. With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result
carries its per-layer metrics (``metrics/<name>.py``) and a breakdown
of device time and idle gaps. The last line of standard output is the
result as one JSON object; the numbers compared and their limits are
also the last lines of standard error.

The run needs a TPU with as many chips as the cell asks for; with
none, or without the repository's sources beside ``bench/``, it exits
non-zero and prints no result. JAX's persistent compilation cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# run as a script, the checkout root takes the place of bench/ on the
# path: the benchmark's modules are imported as the package ``bench``
if sys.path and pathlib.Path(sys.path[0] or ".").resolve() == BENCH:
    sys.path[0] = str(ROOT)

from bench import check, events, peaks  # noqa: E402

# submissions past which a window stops offering load (a guard on the
# per-event arrays; far above any rate one host reaches)
MAX_RATE_EV_S = 400_000
SPANS = ("window", "submit", "generator_sleep", "pipeline_call")
# seconds at the start of a ``--trace 1`` window that the profiler
# records: a TPU trace of this program holds about 1.5 million device
# operations per second and chip, and reading them back takes longer
# than the window
TRACE_SECONDS = 0.5


def log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------- the cell ----
def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """The workload ``name`` of ``<root>/BENCHMARK.json`` with its
    configuration, traffic mix and the metrics it reports, each found
    by name under ``<root>/bench``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    b = root / "bench"
    cfg = json.loads((b / "configs" / f"{wl['config']}.json").read_text())
    mix = json.loads((b / "traffic" / f"{wl['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if name in m.get("workloads", ())
             or ("workloads" not in m and m["moves"] in e2e_names)]
    return {"workload": wl, "config": cfg, "traffic": mix,
            "end_to_end": e2e, "per_layer": layer, "root": root}


def _module(path: pathlib.Path, name: str):
    sp = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def reference(cfg: dict, root: pathlib.Path = ROOT):
    """The configuration's plain reference,
    ``bench/references/<reference>.py``."""
    name = cfg["reference"]
    return _module(root / "bench" / "references" / f"{name}.py",
                   f"bench_reference_{name}")


def reader(metric: str, root: pathlib.Path = ROOT):
    """The per-layer metric's reader, ``bench/metrics/<metric>.py``."""
    return _module(root / "bench" / "metrics" / f"{metric}.py",
                   f"bench_metric_{metric}").read


# ------------------------------------------------------------- platform ----
class CompileMeter:
    """Programs JAX brought to the backend and how many of them came
    from the persistent cache, from JAX's monitoring events (a cache
    hit is reported as a compile too, with its retrieval time)."""

    def __init__(self):
        import jax
        self.compiles, self.hits, self.secs = 0, 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def configure_cache():
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` or the
    checkout's ``.jax_cache``; every program is cached, however fast it
    compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def devices(chips: int, require_chip: bool):
    """The first ``chips`` devices; ``None`` when the cell cannot run
    here (no TPU, or fewer chips than it asks for)."""
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        log(f"no TPU: JAX's devices are {devs[0].platform} "
            f"{devs[0].device_kind!r}; nothing was run")
        return None
    if len(devs) < chips:
        log(f"the cell asks for {chips} chips; JAX finds {len(devs)}")
        return None
    return devs[:chips]


def memory_peak(devs) -> int:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs]
    return int(max(peaks_))


class GcPauses:
    """Pauses of Python's garbage collector, by generation, while
    ``on``: a host stall the load generator and the server share."""

    def __init__(self):
        self.on = False
        self.pauses: list[tuple[int, float]] = []
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def summary(self) -> str:
        full = [d for g, d in self.pauses if g == 2]
        return (f"gc_pauses={len(self.pauses)} "
                f"gc_total_ms={1e3 * sum(d for _, d in self.pauses):.1f} "
                f"gc_full={len(full)} "
                f"gc_full_max_ms={1e3 * max(full, default=0.0):.1f}")

    def close(self):
        gc.callbacks.remove(self._cb)


# ------------------------------------------------------------ the load ----
class Window:
    """Per-event records of one measured window: scheduled and release
    times, release order, failures, and the kept outputs."""

    def __init__(self, cap: int, keep: np.ndarray):
        self.t_sched = np.full(cap, np.nan)
        self.t_release = np.full(cap, np.nan)
        self.failed = np.zeros(cap, bool)
        self.pool_idx = np.zeros(cap, np.int64)
        self.keep = keep
        self.kept: dict[int, dict] = {}
        self.order: list[int] = []
        self.n = 0
        self.big = -1          # pool index of the event with most hits
        self.big_at = None     # the submission that carries it first

    def done(self, i, fut):
        self.t_release[i] = time.perf_counter()
        self.order.append(i)
        if fut.exception() is not None:
            self.failed[i] = True
        elif self.keep[i]:
            self.kept[i] = fut.result()


def _note(win: Window, i: int, j: int):
    win.pool_idx[i] = j
    if j == win.big and win.big_at is None:
        win.big_at = i
        win.keep[i] = True


def offer(svc, pool_events, mix, seconds, seed, win: Window, annotate,
          on_mark=None, mark_s=None):
    """Offer the mix for ``seconds``. Returns the window's start and
    end on the host clock and, for a schedule, how late each submit ran
    after its scheduled time (s). With ``annotate`` the submits and
    sleeps are profiler spans, and a ``window`` span covers the first
    ``mark_s`` seconds, after which ``on_mark(i)`` runs once, with the
    number of events submitted so far."""
    import jax
    span = (jax.profiler.TraceAnnotation if annotate
            else (lambda _name: contextlib.nullcontext()))
    n_pool = len(pool_events)
    order = np.random.default_rng(seed).permutation(n_pool)
    cap = len(win.t_sched)
    lag = []
    marked = [mark_s is None]
    traced = span("window")

    def mark(now, i):
        if not marked[0] and now >= t0 + mark_s:
            marked[0] = True
            traced.__exit__(None, None, None)
            on_mark(i)

    def submit(i, t_sched):
        j = order[i % n_pool]
        win.t_sched[i] = t_sched
        _note(win, i, j)
        with span("submit"):
            f = svc.submit(pool_events[j])
        f.add_done_callback(functools.partial(win.done, i))

    traced.__enter__()
    t0 = time.perf_counter()
    i = 0
    if mix["arrival"] == "backlog":
        end = t0 + seconds
        while i < cap:
            now = time.perf_counter()
            mark(now, i)
            if now >= end:
                break
            submit(i, now)
            i += 1
    elif mix["arrival"] == "poisson":
        at = t0 + events.poisson_arrivals(mix["rate_ev_s"], seconds, seed)
        for i, target in enumerate(at[:cap]):
            now = time.perf_counter()
            mark(now, i)
            if target > now:
                with span("generator_sleep"):
                    time.sleep(target - now)
            lag.append(time.perf_counter() - target)
            submit(i, target)
        i = min(len(at), cap)
        rest = t0 + seconds - time.perf_counter()
        if rest > 0:
            time.sleep(rest)
    else:
        raise ValueError(f"unknown arrival {mix['arrival']!r}")
    win.n = i
    t_end = time.perf_counter()
    mark(np.inf, i)
    return t0, t_end, np.asarray(lag)


def wait_all(win: Window, timeout_s: float = 60.0):
    """Wait until every submitted event has resolved, a minute at
    most past the window's close."""
    t = time.perf_counter()
    while len(win.order) < win.n and time.perf_counter() - t < timeout_s:
        time.sleep(1e-3)


def warm(svc, pool_events, microbatch: int):
    """Every launch shape through the service: full launches and a
    single event."""
    futs = [svc.submit(pool_events[i % len(pool_events)])
            for i in range(4 * microbatch)]
    for f in futs:
        f.result(timeout=600)
    svc.submit(pool_events[0]).result(timeout=600)


def make_inputs(cfg: dict, mix: dict, ref, seed: int, parts: dict):
    """The event pool (with its per-event feed dicts under
    ``"events"``), the calibration events and the weights of a run,
    made from ``seed`` by the reference ``ref``; ``parts`` gets the
    seconds each took."""
    import jax
    t = time.perf_counter()
    det = {**cfg["detector"], **mix.get("detector", {})}
    calib = ref.pool(det, cfg["calibration_events"], seed + 1)
    pool = ref.pool(det, mix["pool"], seed)
    feeds = pool["feeds"]
    pool["events"] = [{k: v[i] for k, v in feeds.items()}
                      for i in range(len(pool["rows"]))]
    parts["event_pool"] = time.perf_counter() - t

    t = time.perf_counter()
    params = ref.weights(seed, cfg["widths"], cfg["weight_gain"], calib)
    jax.block_until_ready(params)
    parts["weights"] = time.perf_counter() - t
    return pool, calib, params


# ---------------------------------------------------------------- a run ----
def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: pathlib.Path = ROOT, require_chip: bool = True,
             fault=None, cell: dict | None = None,
             control: bool = False) -> dict | None:
    """One run; the result dict, or None when the cell cannot run
    here. ``root`` is the checkout whose ``BENCHMARK.json`` and
    ``bench/`` files name the cell; ``fault(fn, params=)`` wraps the
    served pipeline ``fn`` (tests plant faults with it; ``params`` are
    the run's weights); ``require_chip=False`` lets a test drive a run
    on the CPU. ``cell`` replaces the workload looked up
    by name, and ``control`` adds the control's readings on the same
    sample (both for ``readings.py``; a benchmark run uses neither)."""
    cell = cell or load_cell(name, root)
    cfg, mix, wl = cell["config"], cell["traffic"], cell["workload"]
    if not (ROOT / "src" / "repro").is_dir():
        log(f"the repository's sources are not beside bench/ "
            f"({ROOT / 'src'}); nothing was run")
        return None
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    parts = {}
    t = time.perf_counter()
    cache = configure_cache()
    import jax
    devs = devices(wl["chips"], require_chip)
    if devs is None:
        return None
    meter = CompileMeter()
    parts["jax_init"] = time.perf_counter() - t

    from bench import sut
    ref = reference(cfg, root)
    w = cfg["widths"]
    pool, calib, params = make_inputs(cfg, mix, ref, seed, parts)
    pool_events = pool["events"]

    t = time.perf_counter()
    served = sut.build(cfg, params, calib["feeds"], annotate=trace,
                       fault=fault and functools.partial(fault, params=params))
    parts["deploy"] = time.perf_counter() - t
    svc = served.service
    if require_chip and served.backend != "pallas":
        svc.close()
        raise RuntimeError(f"the served kernels run {served.backend!r}, "
                           f"not the compiled Pallas kernels")
    t = time.perf_counter()
    warm(svc, pool_events, svc.microbatch)
    parts["warmup"] = time.perf_counter() - t
    setup_s = time.perf_counter() - T_PROCESS
    log("setup " + " ".join(f"{k}={v:.3f}s" for k, v in parts.items())
        + f" programs={meter.compiles} cache_hits={meter.hits} "
        f"compiled={meter.compiles - meter.hits} "
        f"compile_s={meter.secs:.3f} setup_s={setup_s:.3f} cache={cache}")

    # ------------------------------------------------------- the window
    cap = int(MAX_RATE_EV_S * seconds) + 1
    keep = np.random.default_rng(seed + 2).random(cap) < mix["keep_share"]
    win = Window(cap, keep)
    win.big = int(np.argmax(pool["rows"]))
    launch = served.launch
    stats0 = _counters(svc)
    timed0 = (served.timed.calls, served.timed.seconds)
    compiles0 = meter.compiles
    pauses = GcPauses()
    pauses.on = True
    tdir = ROOT / ".bench_trace" / f"{name}-{seed}"
    marked = {}

    def on_mark(i):
        # the traced part of the window ends: per-layer counts are
        # taken over it
        marked.update(t=time.perf_counter(), i=i, stats=_counters(svc),
                      timed=(served.timed.calls, served.timed.seconds))
        jax.profiler.stop_trace()

    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    t0, t_end, lag = offer(svc, pool_events, mix, seconds, seed, win,
                           annotate=trace, on_mark=on_mark if trace else None,
                           mark_s=TRACE_SECONDS if trace else None)
    compiles_window = meter.compiles - compiles0
    pauses.on = False
    pauses.close()
    wait_all(win)
    mem = memory_peak(devs)
    svc.close()
    log("replicas " + " ".join(
        f"{r.replica_id}:{r.stats.completed}@"
        f"{','.join(sorted(str(d) for d in r.stats.devices))}"
        for r in svc.replicas))
    if trace:
        queue_wait = _queue_waits(svc, stats0, marked["stats"])
    del svc, served
    gc.collect()

    # -------------------------------------------------- end-to-end numbers
    n = win.n
    rel = win.t_release[:n]
    in_window = (rel >= t0) & (rel <= t0 + seconds)
    failed = int(win.failed[:n].sum())
    lat = np.where(win.failed[:n] | np.isnan(rel), np.inf,
                   rel - win.t_sched[:n])
    values = {"events_per_s": float(in_window.sum()) / seconds,
              "setup_s": setup_s}
    if np.isfinite(lat).any():
        for q in (50, 90, 99):
            values[f"p{q}_latency_us"] = float(np.percentile(lat, q)) * 1e6
        log("latency " + " ".join(f"p{q}={values[f'p{q}_latency_us']:.1f}us"
                                  for q in (50, 90, 99)))

    # --------------------------------------------------------- correctness
    t = time.perf_counter()
    verdict = check.judge(cfg, ref, params, pool, win, seed,
                          with_control=control)
    check_s = time.perf_counter() - t
    log(f"window events={n} released_in_window={int(in_window.sum())} "
        f"failed={failed} programs_in_window={compiles_window} "
        f"{pauses.summary()} check_s={check_s:.3f} "
        f"sampled={verdict['sampled']} tie_events={verdict['tie_events']} "
        f"hit_gap={verdict['hit_gap']!r}")

    # ------------------------------------------------------------- metrics
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    out = {"correct": verdict["correct"], "attempted": int(n),
           "failed": failed, "metrics": {}, "device": device}
    if trace:
        # a CPU run (tests only) has no peaks: readers that need them
        # report nothing
        pk = (peaks.peaks(devs[0].device_kind)
              if require_chip or devs[0].device_kind in peaks.PEAKS
              else None)
        span_s = marked["t"] - t0
        traced = (rel >= t0) & (rel <= marked["t"])
        rows_of = pool["rows"][win.pool_idx[:n][traced]]
        s1, tm1 = marked["stats"], marked["timed"]
        record = {
            "seconds": span_s, "chips": len(devs), "peaks": pk,
            "widths": w, "launch": launch,
            "batches": s1["batches"] - stats0["batches"],
            "completed": s1["completed"] - stats0["completed"],
            "pipeline_calls": tm1[0] - timed0[0],
            "pipeline_seconds": tm1[1] - timed0[1],
            "queue_wait_s": queue_wait, "lag_s": lag[:marked["i"]],
            "model_flops": float(sum(ref.model_flops(int(h), w)
                                     for h in rows_of)),
            "trace": _read_trace(tdir, devs),
        }
        shutil.rmtree(tdir, ignore_errors=True)
        tr = record["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        for m in cell["per_layer"]:
            v = reader(m["name"], root)(record)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": tr["top_ops"],
                            "idle_gaps": tr["gaps"]}
    else:
        for m in cell["end_to_end"]:
            if m["name"] in values:
                out["metrics"][m["name"]] = {"value": values[m["name"]],
                                             "unit": m["unit"]}
    if control:
        out["control"] = verdict["control"]
    out["compared"] = verdict["compared"]
    return out


def _counters(svc) -> dict:
    per = [r.stats.completed for r in svc.replicas]
    return {"completed": sum(per), "per_replica": per,
            "batches": sum(r.stats.batches for r in svc.replicas)}


def _queue_waits(svc, since, until) -> list:
    """Queue waits (s) of the events each replica released between the
    counts ``since`` and ``until``, from its sample window (which keeps
    the newest samples)."""
    out = []
    for r, a, b in zip(svc.replicas, since["per_replica"],
                       until["per_replica"]):
        xs = r.stats.samples("queue_wait_s")
        first = r.stats.completed - len(xs)     # count of xs[0]
        out.extend(xs[max(0, a - first):max(0, b - first)])
    return out


def _read_trace(tdir, devs) -> dict:
    from bench import trace as tr_mod
    tr = tr_mod.load(tr_mod.find_xplane(str(tdir)), SPANS)
    win = [s for s in tr["host"] if s[2] == "window"]
    if not win:
        raise RuntimeError("the trace holds no window span")
    t0, t1 = win[0][0], win[0][0] + win[0][1]
    planes = sorted(tr["devices"], key=lambda p: int(p.rsplit(":", 1)[1]))
    ids = {d.id for d in devs}
    used = [p for p in planes if int(p.rsplit(":", 1)[1]) in ids] or planes
    sub = {"devices": {p: tr["devices"][p] for p in used},
           "host": tr["host"]}
    busy = tr_mod.device_busy(sub, t0, t1)
    return {"trace": sub, "t0": t0, "t1": t1,
            "window_s": (t1 - t0) * 1e-9,
            "busy_s": float(np.mean(list(busy.values()))) if busy else None,
            "top_ops": tr_mod.top_ops(sub, t0, t1),
            "gaps": tr_mod.idle_gaps(sub, t0, t1)}


# ----------------------------------------------------------------- main ----
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    if out is None:
        return 1
    for k, v in out["compared"].items():
        print(f"[bench] compared {k}={v}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
