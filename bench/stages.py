"""The program's own spans and op scopes in a profiler trace, and runs
of a cell that read them out.

The served program (``src/repro``) opens ``trigger.*`` spans per
launch and stage (``repro/spans.py``) and lowers every graph op under
``jax.named_scope(<op name>)``, so each compiled instruction's ``op_name``
metadata names the op it came from. ``load`` reads a ``.xplane.pb``
into the dict ``trace.load`` gives, with two more keys:

    {"devices": {plane: [[start_ns, dur_ns, op_name], ...]},
     "scopes": {plane: [scope, ...]},      # parallel to devices[plane]
     "host": [[start_ns, dur_ns, span_name], ...],
     "program": [[start_ns, dur_ns, span_name, thread, stats], ...]}

A scope is the graph op's name (``cps``, ``gn0_agg.block``), or
``UNSCOPED`` for an operation outside every op (the loop of a
``lax.map`` over chunks, copies XLA adds). ``thread`` tells host
threads apart; ``stats`` are the span's keyword stats.

Nested operations: a TPU trace lists a ``while`` and the operations of
its body on one line, the body's inside the loop's interval. A
scope's *self time* counts each operation's duration less that of the
operations nested directly inside it, so the self times of all scopes
add up to the busy time and nothing is counted twice.

    python bench/stages.py --workload <cell> --seeds 1 2 --seconds 30 \\
        --trace 0|1 [--out stages.jsonl]

runs the cell as ``bench/run.py`` does, once per seed, and prints per
run its result and each lane's stage counters over the window
(``count``, ``total_s``, ``max_s``); a ``--trace 1`` run adds the
device self time by scope, the idle gaps labelled by program span,
and the program spans' totals. A TPU trace names a device event by
its HLO instruction alone, with no ``op_name``: a traced run has XLA
dump every module it compiles (the persistent cache is off for it)
and reads the scopes from the dumped text. ``--helper-cost`` times
the stage helper with no profiler running.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import re
import shutil
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
if sys.path and pathlib.Path(sys.path[0] or ".").resolve() == BENCH:
    sys.path[0] = str(BENCH.parent)
if str(BENCH.parent / "src") not in sys.path:
    sys.path.insert(0, str(BENCH.parent / "src"))

from bench import trace as trace_mod  # noqa: E402

PREFIX = "trigger."
UNSCOPED = "(unscoped)"
OP_NAME = re.compile(r'op_name="([^"]*)"')
# name-stack frames JAX adds around and inside an op: transformations
# with their argument (``jit(whole)``, ``vmap()``) and control flow
JAX_FRAME = re.compile(r"^(\w+\(.*\)|while|body|cond|closed_call|"
                       r"core_call|remat|checkpoint|branch_\d+)$")


def scope_of(op_name: str) -> str:
    """The graph op scope in an ``op_name`` path: the first frame
    after the leading JAX frames, when something follows it (the last
    frame is the primitive's own name)."""
    parts = [p for p in op_name.split(";")[0].split("/") if p]
    i = 0
    while i < len(parts) and JAX_FRAME.match(parts[i]):
        i += 1
    return parts[i] if len(parts) - i >= 2 else UNSCOPED


HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%([^\s(]+) .*\{\s*$")
HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = ")
# computations an instruction calls: a fusion's body, a loop's body and
# condition, a reduction's combiner, a conditional's branches
HLO_CALLS = re.compile(r"(?:calls|body|condition|to_apply)=%([^\s,)}]+)"
                       r"|(?:called_computations|branch_computations)"
                       r"=\{([^}]*)\}")
MODULES_LINE = "XLA Modules"
MODULE_ID = re.compile(r"^(.*?)\((\d+)\)$")


def hlo_scopes(text: str) -> tuple[str, dict]:
    """A compiled HLO module's name and the scope of each of its
    instructions. An instruction takes the scope of its ``op_name``
    metadata; one without (XLA drops it on some fusions it makes) takes
    the commonest scope among the computations it calls, and the
    instructions of a computation still without one take the scope of
    the instruction that calls it (a loop's body, its loop's)."""
    m = HLO_MODULE.search(text)
    comp_of, own, calls = {}, {}, {}
    members: dict[str, list] = {}
    comp = None
    for line in text.splitlines():
        cm = HLO_COMPUTATION.match(line)
        if cm:
            comp = cm.group(1)
            members[comp] = []
            continue
        im = HLO_INSTR.match(line)
        if im is None or comp is None:
            continue
        name = im.group(1)
        comp_of[name] = comp
        members[comp].append(name)
        om = OP_NAME.search(line)
        sc = scope_of(om.group(1)) if om else UNSCOPED
        own[name] = None if sc == UNSCOPED else sc
        calls[name] = [c.strip().lstrip("%") for a, b in HLO_CALLS.findall(
            line) for c in ([a] if a else b.split(","))]

    def from_callees(name, seen):
        if own[name] is not None or name in seen:
            return own[name]
        seen.add(name)
        votes: dict[str, int] = {}
        for c in calls[name]:
            for n in members.get(c, ()):
                sc = from_callees(n, seen)
                if sc is not None:
                    votes[sc] = votes.get(sc, 0) + 1
        own[name] = max(votes, key=votes.get) if votes else None
        return own[name]

    for name in own:
        from_callees(name, set())
    stack = [n for n in own if own[n] is not None]
    while stack:
        name = stack.pop()
        for c in calls[name]:
            for n in members.get(c, ()):
                if own[n] is None:
                    own[n] = own[name]
                    stack.append(n)
    return (m.group(1) if m else ""), {
        n: sc for n, sc in own.items() if sc is not None}


def hlo_dump_scopes(dump_dir) -> dict:
    """``{module name: {instruction: scope}}`` from the final HLO text
    of every module XLA dumped into ``dump_dir`` (``--xla_dump_to``
    with ``--xla_dump_hlo_as_text``): the TPU's after-codegen text, or
    the after-optimizations text where there is none."""
    d = pathlib.Path(dump_dir)
    files = sorted(d.glob("*after_codegen.txt")) \
        or sorted(d.glob("*after_optimizations.txt"))
    out: dict[str, dict] = {}
    for f in files:
        name, by = hlo_scopes(f.read_text())
        out.setdefault(name, {}).update(by)
    return out


def _module_of(modules, t):
    """The module execution ``[start, end, name]`` (sorted by start)
    that holds time ``t``, or None."""
    import bisect
    i = bisect.bisect_right(modules, [t, float("inf")]) - 1
    return modules[i][2] if i >= 0 and modules[i][1] > t else None


def load(path: str, span_names, hlo: dict | None = None) -> dict:
    """``trace.load``'s device operations and named host spans, the
    scope of each operation, and every ``trigger.*`` span. A TPU
    device event carries no ``op_name`` (its stats are its offset and
    duration): ``hlo`` (``{module name: {instruction: scope}}``, from
    ``hlo_dump_scopes``) gives the scope of the instruction in the
    module executing at the time; without it every scope is
    ``UNSCOPED``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    names = set(span_names)
    hlo = hlo or {}
    devices, scopes, host, program = {}, {}, [], []
    for plane in pd.planes:
        if trace_mod.DEVICE_PLANE.match(plane.name):
            modules = sorted(
                [float(e.start_ns), float(e.start_ns + e.duration_ns),
                 MODULE_ID.sub(r"\1", e.name)]
                for line in plane.lines if line.name == MODULES_LINE
                for e in line.events)
            evs = []
            for line in plane.lines:
                if line.name == trace_mod.OPS_LINE:
                    for e in line.events:
                        name = trace_mod.op_name(e.name)
                        mod = _module_of(modules, e.start_ns)
                        evs.append((float(e.start_ns),
                                    float(e.duration_ns), name,
                                    hlo.get(mod, {}).get(name, UNSCOPED)))
            evs.sort()
            devices[plane.name] = [[s, d, n] for s, d, n, _ in evs]
            scopes[plane.name] = [sc for *_, sc in evs]
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                thread = f"{plane.name}#{i}"
                for e in line.events:
                    if e.name in names:
                        host.append([float(e.start_ns),
                                     float(e.duration_ns), e.name])
                    elif e.name.startswith(PREFIX):
                        program.append([float(e.start_ns),
                                        float(e.duration_ns), e.name,
                                        thread, dict(e.stats)])
    return {"devices": devices, "scopes": scopes, "host": sorted(host),
            "program": sorted(program, key=lambda r: r[:3])}


def self_times(ops) -> list[float]:
    """Each operation's duration (ns) less the durations of the
    operations nested directly inside it; ``ops`` are one line's
    ``[start, dur, ...]``, sorted by start."""
    out = [float(o[1]) for o in ops]
    stack: list[int] = []     # enclosing operations, innermost last
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    for i in order:
        s, d = ops[i][0], ops[i][1]
        while stack and ops[stack[-1]][0] + ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and s + d <= ops[stack[-1]][0] + ops[stack[-1]][1]:
            out[stack[-1]] -= d
        stack.append(i)
    return out


def device_scopes(trace, t0, t1, n=10) -> list:
    """Device self seconds by scope, of the operations that start in
    the window, summed over planes; the ``n`` largest."""
    acc: dict[str, float] = {}
    for plane, ops in trace["devices"].items():
        for o, sc, st in zip(ops, trace["scopes"][plane], self_times(ops)):
            if t0 <= o[0] < t1:
                acc[sc] = acc.get(sc, 0.0) + st * 1e-9
    return sorted(([k, v] for k, v in acc.items()),
                  key=lambda kv: -kv[1])[:n]


def scope_self_time(trace, scope, t0, t1) -> float:
    """Device self seconds of the operations under ``scope`` that
    start in the window, summed over planes."""
    return dict(device_scopes(trace, t0, t1, n=None)).get(scope, 0.0)


def span_time(trace, names, t0, t1) -> tuple[float, int]:
    """Total seconds and count of the program spans named in
    ``names`` that start in the window."""
    names = {names} if isinstance(names, str) else set(names)
    secs, n = 0.0, 0
    for s, d, name, *_ in trace["program"]:
        if name in names and t0 <= s < t1:
            secs += d * 1e-9
            n += 1
    return secs, n


def span_stat(trace, name, stat, t0, t1) -> float:
    """Sum of the stat ``stat`` over the spans ``name`` that start in
    the window."""
    return float(sum(r[4].get(stat, 0) for r in trace["program"]
                     if r[2] == name and t0 <= r[0] < t1))


def idle_gaps(trace, t0, t1, n=10) -> list:
    """Idle time between device operations in the window, summed by
    label over every device plane, longest first. A gap is labelled by
    the program span covering its midpoint that started last; where no
    program span covers it, by ``trace.idle_gaps``' rule over the
    benchmark's host spans."""
    prog = trace_mod._coverage([r[:3] for r in trace["program"]])
    bench = trace_mod._coverage(trace["host"])
    acc: dict[str, float] = {}
    for ops in trace["devices"].values():
        busy = trace_mod.busy_intervals(ops, t0, t1)
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                mid = 0.5 * (a + b)
                lab = trace_mod._label(prog, mid)
                if lab == "no span":
                    lab = trace_mod._label(bench, mid)
                acc[lab] = acc.get(lab, 0.0) + (b - a) * 1e-9
    return sorted(([k, v] for k, v in acc.items()),
                  key=lambda kv: -kv[1])[:n]


def threads(trace) -> dict[str, set]:
    """The span names seen on each host thread."""
    out: dict[str, set] = {}
    for r in trace["program"]:
        out.setdefault(r[3], set()).add(r[2])
    return out


def summary(trace, t0, t1) -> dict:
    """What a traced window shows: busy seconds and self seconds by
    scope per chip (mean over planes), the labelled idle gaps, each
    program span's count and seconds, and the candidate per-layer
    numbers read from them."""
    planes = max(len(trace["devices"]), 1)
    busy = trace_mod.device_busy(trace, t0, t1)
    scopes = device_scopes(trace, t0, t1, n=None)
    spans: dict[str, list] = {}
    for s, d, name, *_ in trace["program"]:
        if t0 <= s < t1:
            c = spans.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += d * 1e-9
    busy_s = sum(busy.values())
    gaps = idle_gaps(trace, t0, t1, n=None)
    idle_s = sum(v for _, v in gaps)
    named = sum(v for k, v in gaps if k.startswith(PREFIX))
    out = {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": busy_s / planes,
        "device_scopes": scopes[:10],
        "scope_sum_over_busy": (sum(v for _, v in scopes) / busy_s
                                if busy_s else None),
        "cps_device_share_pct": (100.0 * dict(scopes).get("cps", 0.0)
                                 / busy_s if busy_s else None),
        "idle_gaps": gaps[:10],
        "idle_labelled_by_program_pct": (100.0 * named / idle_s
                                         if idle_s else None),
        "spans": {k: {"count": c, "seconds": v}
                  for k, (c, v) in sorted(spans.items())},
        "released_in_window": span_stat(trace, PREFIX + "release",
                                        "events", t0, t1),
        "threads": {t: sorted(v) for t, v in threads(trace).items()},
    }
    launches = spans.get(PREFIX + "pipeline", [0])[0]
    harvest = sum(spans.get(PREFIX + k, [0, 0.0])[1]
                  for k in ("fetch", "release"))
    out["harvest_host_us"] = (harvest / launches * 1e6 if launches
                              else None)
    calls = spans.get(PREFIX + "ragged.pack", [0])[0]
    ragged = sum(spans.get(PREFIX + k, [0, 0.0])[1]
                 for k in ("ragged.pack", "ragged.unpack"))
    out["ragged_host_us"] = ragged / calls * 1e6 if calls else None
    return out


# ------------------------------------------------------------- the runs ----
def helper_cost(n: int = 100_000) -> dict:
    """Microseconds, with no profiler running, of one launch's lane
    stages as the streaming loop opens them, and of one bare pipeline
    span."""
    from jax.profiler import TraceAnnotation

    from repro import spans
    c = spans.StageCounters()
    t = time.perf_counter()
    for _ in range(n):
        with spans.stage(spans.SLOT_WAIT, c):
            pass
        with spans.stage(spans.PACK, c) as st:
            st.set(events=16, queued=40)
        with spans.stage(spans.PIPELINE, c, events=16):
            pass
        with spans.stage(spans.RESULT_WAIT, c):
            pass
        with spans.stage(spans.DEVICE_WAIT, c):
            pass
        with spans.stage(spans.FETCH, c):
            pass
        with spans.stage(spans.RELEASE, c, events=16) as st:
            st.set(held=0)
    launch_us = (time.perf_counter() - t) / n * 1e6
    t = time.perf_counter()
    for _ in range(n):
        with TraceAnnotation(spans.PIPELINE_CHUNK, rows=1):
            pass
    span_us = (time.perf_counter() - t) / n * 1e6
    return {"lane_launch_us": launch_us, "span_us": span_us}


def run(workload: str, seed: int, seconds: float, trace: bool,
        hlo_dump=None, **kw) -> dict:
    """One run of ``bench/run.py``'s ``run_cell`` (``kw`` are its
    keyword arguments), with the service, the lane counters over the
    window and, traced, the whole trace kept. ``hlo_dump`` is the
    directory XLA dumps the compiled modules into, for the scopes of
    device events that carry no ``op_name``."""
    from bench import run as run_mod
    from bench import sut
    from repro import spans
    kept: dict = {}
    build, offer, load_ = sut.build, run_mod.offer, trace_mod.load

    def build_kept(*a, **k):
        kept["served"] = served = build(*a, **k)
        return served

    def offer_fresh(svc, *a, **k):
        # the window's own counters: fresh at its start
        for r in svc.replicas:
            r.stats.stages = spans.StageCounters()
        return offer(svc, *a, **k)

    def load_all(path, span_names):
        hlo = hlo_dump_scopes(hlo_dump) if hlo_dump else None
        kept["trace"] = tr = load(path, span_names, hlo=hlo)
        return tr

    sut.build, run_mod.offer, trace_mod.load = build_kept, offer_fresh, \
        load_all
    try:
        out = run_mod.run_cell(workload, seed, seconds, trace, **kw)
    finally:
        sut.build, run_mod.offer, trace_mod.load = build, offer, load_
    if out is None:
        raise SystemExit(f"{workload}: the cell cannot run here")
    res = {"workload": workload, "seed": seed, "trace": int(trace),
           "correct": out["correct"], "metrics": out["metrics"],
           "device": out["device"]}
    lanes = []
    for r in kept["served"].service.replicas:
        snap = r.stats.stages.snapshot()
        top = max(snap.items(), key=lambda kv: kv[1]["max_s"],
                  default=(None, None))
        lanes.append({"replica": r.replica_id, "stages": snap,
                      "largest_max": [top[0], top[1] and top[1]["max_s"]]})
    res["lanes"] = lanes
    if trace:
        tr = kept["trace"]
        win = [s for s in tr["host"] if s[2] == "window"]
        t0, t1 = win[0][0], win[0][0] + win[0][1]
        res["program"] = summary(tr, t0, t1)
        res["breakdown"] = out.get("breakdown")
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--helper-cost", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    rows = []
    dump = None
    if args.trace and args.workload:
        # a TPU trace names each device event by its instruction alone:
        # XLA's dump of every module compiled in this process maps the
        # instructions to their scopes, so nothing may come from the
        # persistent cache (the flags are read when the backend starts)
        dump = BENCH.parent / ".bench_trace" / f"hlo-{os.getpid()}"
        os.environ["XLA_FLAGS"] = " ".join(filter(None, [
            os.environ.get("XLA_FLAGS"), f"--xla_dump_to={dump}",
            "--xla_dump_hlo_as_text"]))
        import jax
        jax.config.update("jax_enable_compilation_cache", False)
    if args.helper_cost:
        rows.append({"helper_cost": helper_cost()})
    try:
        for seed in args.seeds if args.workload else []:
            rows.append(run(args.workload, seed, args.seconds,
                            bool(args.trace), hlo_dump=dump))
    finally:
        if dump is not None:
            shutil.rmtree(dump, ignore_errors=True)
    for row in rows:
        line = json.dumps(row, default=str)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
