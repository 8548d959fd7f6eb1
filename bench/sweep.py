"""The knee of a configuration: open-loop Poisson windows at fixed
rates, one after another, against one built service, in one process.

    python bench/sweep.py --config ccn-upgrade-fp --seconds 4 \
        --rates 1000 2000 3000

For each rate: the rate the service released at, how many events were
still unreleased when the window closed (a backlog that grows through
the window shows as a count that rises with the window), and the
median and 99th percentile latency from scheduled arrival. The knee is
the highest rate at which the released rate equals the offered one
and nothing piles up; ``traffic/steady.json`` offers 0.8 of it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
if sys.path and pathlib.Path(sys.path[0] or ".").resolve() == BENCH:
    sys.path[0] = str(BENCH.parent)

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="steady")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    b = run.ROOT / "bench"
    cfg = json.loads((b / "configs" / f"{args.config}.json").read_text())
    mix = json.loads((b / "traffic" / f"{args.traffic}.json").read_text())
    sys.path.insert(0, str(run.ROOT / "src"))
    run.configure_cache()
    devs = run.devices(cfg["chips"], require_chip=True)
    if devs is None:
        return 1
    from bench import sut
    pool, calib, params = run.make_inputs(cfg, mix, run.reference(cfg),
                                          args.seed, {})
    pool_events = pool["events"]
    served = sut.build(cfg, params, calib["feeds"], annotate=False)
    svc = served.service
    run.warm(svc, pool_events, svc.microbatch)
    try:
        for rate in args.rates:
            cap = int(rate * args.seconds) + 1
            win = run.Window(cap, np.zeros(cap, bool))
            m = {**mix, "arrival": "poisson", "rate_ev_s": rate}
            t0, t_end, lag = run.offer(svc, pool_events, m, args.seconds,
                                       args.seed, win, annotate=False)
            pending = win.n - len(win.order)
            run.wait_all(win)
            rel = win.t_release[:win.n]
            lat = rel - win.t_sched[:win.n]
            done = np.sum(rel <= t_end)
            print(json.dumps({
                "rate_ev_s": rate, "offered": win.n,
                "released_ev_s": float(done / (t_end - t0)),
                "unreleased_at_close": int(pending),
                "p50_us": float(np.percentile(lat, 50) * 1e6),
                "p99_us": float(np.percentile(lat, 99) * 1e6),
                "lag_p99_us": float(np.percentile(lag, 99) * 1e6)}),
                flush=True)
            time.sleep(0.5)
    finally:
        svc.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
