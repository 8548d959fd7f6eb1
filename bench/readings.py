"""Readings that set the limits of ``correct``: the numbers compared
(``check.py``; which of them a cell has follows from what its
configuration's reference gives), for the program and for the control
(that reference one precision lower), over several seeds, in one
process.

    python bench/readings.py --config ccn-upgrade-fp --traffic backlog \
        --seconds 1 --seeds 11 12 13

Each seed is one short run of the cell at its own load (``run.py``
with the control's readings added on the same sample); a configuration
need not be in ``BENCHMARK.json``, and may be of any model with a
reference in ``references/``. One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
if sys.path and pathlib.Path(sys.path[0] or ".").resolve() == BENCH:
    sys.path[0] = str(BENCH.parent)

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    b = run.ROOT / "bench"
    cfg = json.loads((b / "configs" / f"{args.config}.json").read_text())
    mix = json.loads((b / "traffic" / f"{args.traffic}.json").read_text())
    name = f"{args.config}.{args.traffic}"
    cell = {"workload": {"name": name, "config": args.config,
                         "traffic": args.traffic, "chips": cfg["chips"]},
            "config": cfg, "traffic": mix, "end_to_end": [],
            "per_layer": [], "root": run.ROOT}
    for seed in args.seeds:
        out = run.run_cell(name, seed, args.seconds, False, cell=cell,
                           control=True)
        if out is None:
            return 1
        print(json.dumps({"cell": name, "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "program": {k: v["value"] for k, v in
                                      out["compared"].items()},
                          "control": out["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
