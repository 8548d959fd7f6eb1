"""A checkout for the harness tests: the benchmark's files plus the
test-size configurations, traffic, metric and reference of ``data/``,
dropped into their directories, and a ``BENCHMARK.json`` naming
them."""
from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"


def spec(cells):
    """A benchmark of ``cells`` ((config, traffic) pairs) with the
    test metric on every cell."""
    names = [f"{c}.{t}" for c, t in cells]
    return {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "workloads": [{"name": n, "config": c, "traffic": t, "chips": 1,
                       "why": "test"} for n, (c, t) in zip(names, cells)],
        "end_to_end": [
            {"name": "events_per_s", "unit": "events/s", "better": "higher",
             "bound": 0.05, "source": "host_clock"},
            {"name": "p50_latency_us", "unit": "us", "better": "lower",
             "bound": 0.1, "source": "host_clock",
             "workloads": [n for n, (_, t) in zip(names, cells)
                           if "poisson" in t]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "launches.test", "unit": "launches", "better": "higher",
             "source": "program_counter", "layer": "serving",
             "moves": "events_per_s", "workloads": names},
            {"name": "batch_fill.backlog", "unit": "events/launch",
             "better": "higher", "source": "program_counter",
             "layer": "serving", "moves": "events_per_s",
             "workloads": names}]}


def checkout(tmp: pathlib.Path, cells) -> pathlib.Path:
    """``tmp`` as a checkout: ``bench/`` without its tests, the data
    files dropped into ``configs/``, ``traffic/``, ``metrics/`` and
    ``references/``."""
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for sub in ("configs", "traffic", "metrics", "references"):
        for f in (DATA / sub).glob("*.*"):
            shutil.copy(f, tmp / "bench" / sub / f.name)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec(cells)))
    return tmp
