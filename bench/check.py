"""The comparison that decides ``correct``.

A sample of the events the timed path released, drawn from the seed
(and always holding the pool's event with the most rows), is run
through the configuration's plain reference (``references/<name>.py``)
at the configuration's stated precision, and compared. The reference
names the heads and each event's real rows (hits, nodes). A row's gap
in a head is the largest ``|served - reference|`` over the head's
components, divided by the head's RMS over the sample's real rows.

- ``head_gap``: for each sampled event and each head, the median of
  its real rows' gaps; the largest over heads and events. A head that
  is wrong on most rows of any event shows here, whatever the other
  heads read.
- ``hits_off``: the real rows whose gap in some head exceeds
  ``hit_tolerance``. Compared exactly. Where the reference gives each
  event a tie margin (CaloClusterNet: how near its k-nearest
  selections came to a tie), only the events whose margin exceeds
  ``knn_tie_margin`` count gaps: there a neighbour cannot flip by
  rounding, and rounding moves a row by far less than the tolerance,
  so one wrong row shows. In the others only a NaN is counted.
- ``decision_miss``, only where the reference gives a decision (the
  CCN trigger): the share of sampled events whose decision differs
  from the reference's, among the events whose decision the reference
  does not take within ``decision_margin`` of its threshold: a
  decision that rounding can turn either way is not counted. Compared
  exactly.
- ``order_breaks``: events released out of submission order, released
  twice, or never; ``unanswered``: events that failed. Both exact.

``readings`` gives the same numbers for any outputs, so the control
(the reference with every dense matmul as three bfloat16 products, the
precision below float32 at ``highest``) is read with the same code.
"""
from __future__ import annotations

import numpy as np

# CaloClusterNet's heads, as its reference names them (the planted
# faults of the harness tests move them by name)
HEADS = ("beta", "coords", "energy", "cls")


def _scales(heads, mask):
    real = mask > 0
    return {h: max(float(np.sqrt(np.mean(a[real] ** 2))), 1e-6)
            for h, a in heads.items()}


def readings(got, want, decision_margin=0.0, knn_tie_margin=0.0,
             hit_tolerance=np.inf) -> dict:
    """The numbers compared, of outputs ``got`` (``{"heads": {head: (E,
    rows, d)}}``, and ``"decision"`` ``(E,)`` where the reference gives
    one) against the reference's ``want`` (its ``expected`` dict). Also,
    not compared: ``hit_gap``, the largest gap of a row that
    ``hits_off`` counts over, and ``tie_events``, the events it leaves
    out."""
    heads, mask = want["heads"], want["mask"]
    sc = _scales(heads, mask)
    ties_known = "tie_margin" in want
    medians, top, off, ties = [0.0], 0.0, 0, 0
    for e in range(mask.shape[0]):
        real = mask[e] > 0
        if not real.any():
            continue
        gaps = np.stack([
            np.max(np.abs(got["heads"][h][e][real] - heads[h][e][real]),
                   axis=-1) / sc[h] for h in heads])
        gaps = np.nan_to_num(gaps, nan=np.inf)       # a NaN is wrong
        medians.append(float(np.median(gaps, axis=1).max()))
        if not ties_known or want["tie_margin"][e] > knn_tie_margin:
            worst = gaps.max(axis=0)
            top = max(top, float(worst.max()))
            off += int(np.sum(worst > hit_tolerance))
        else:       # a neighbour may flip here: only a NaN is counted
            ties += 1
            off += int(np.sum(np.isinf(gaps).any(axis=0)))
    out = {"head_gap": max(medians), "hits_off": off}
    if "decision" in want:
        miss = np.asarray(got["decision"], bool) != want["decision"]
        miss &= np.abs(want["decision_margin"]) > decision_margin
        out["decision_miss"] = float(miss.mean())
        out["triggers"] = int(want["decision"].sum())
    return {**out, "hit_gap": top, "tie_events": ties,
            "events": int(mask.shape[0])}


def sample(win, seed: int, size: int) -> list[int]:
    """Submission indices to compare: ``size`` of the kept outputs,
    drawn from the seed, with the largest pool event's first
    submission among them."""
    kept = sorted(win.kept)
    rng = np.random.default_rng(seed + 3)
    pick = sorted(rng.choice(kept, size=min(size, len(kept)),
                             replace=False).tolist()) if kept else []
    if win.big_at is not None and win.big_at in win.kept \
            and win.big_at not in pick:
        pick.append(win.big_at)
    return pick


def _stack(outs, want):
    """The served events' heads (and decisions), as ``readings`` takes
    them, from the reference's ``served`` reading of each."""
    n_rows = want["mask"].shape[1]
    heads = {}
    for h in want["heads"]:
        arrs = []
        for o in outs:
            a = np.asarray(o["heads"][h], np.float32)
            if a.shape[0] < n_rows:      # per-row outputs a bucket cut off
                a = np.pad(a, [(0, n_rows - a.shape[0])]
                           + [(0, 0)] * (a.ndim - 1))
            arrs.append(a[:n_rows])
        heads[h] = np.stack(arrs)
    got = {"heads": heads}
    if "decision" in want:
        got["decision"] = np.array([o["decision"] for o in outs])
    return got


def expected(ref, params, data, w, precision: str,
             control: bool = False) -> dict:
    """The reference's ``expected`` on the sampled events ``data`` at a
    matmul precision; with ``control``, the control's."""
    import jax
    with jax.default_matmul_precision(precision):
        return ref.expected(params, data, w, control=control)


def judge(cfg, ref, params, pool, win, seed, *,
          with_control=False) -> dict:
    """``correct`` and every number compared, with its limit; with
    ``with_control``, also the control's readings on the same sample.
    ``ref`` is the reference module, ``pool`` its ``pool`` of the
    run's events."""
    n = win.n
    order_breaks = int(np.sum(np.asarray(win.order) != np.arange(len(
        win.order)))) + abs(n - len(win.order))
    unanswered = int(win.failed[:n].sum())
    w, chk = cfg["widths"], cfg["check"]
    idx = sample(win, seed, chk["sample"])
    got = [ref.served(win.kept[i]) for i in idx]
    pj = win.pool_idx[idx]
    data = {k: v[pj] for k, v in pool["data"].items()}
    margins = {k: chk[k] for k in ("decision_margin", "knn_tie_margin",
                                   "hit_tolerance") if k in chk}
    r = {"head_gap": 0.0, "hits_off": 0, "events": 0, "tie_events": 0,
         "hit_gap": 0.0}
    ctrl = None
    if got:
        want = expected(ref, params, data, w, chk["precision"])
        r = readings(_stack(got, want), want, **margins)
        if with_control:
            c = expected(ref, params, data, w, chk["precision"],
                         control=True)
            ctrl = readings(c, want, **margins)
    compared = {
        "head_gap": {"value": r["head_gap"],
                     "limit": chk["limits"]["head_gap"]},
        "hits_off": {"value": r["hits_off"], "limit": 0},
    }
    if "decision_miss" in r:
        compared["decision_miss"] = {"value": r["decision_miss"], "limit": 0}
    compared["order_breaks"] = {"value": order_breaks, "limit": 0}
    compared["unanswered"] = {"value": unanswered, "limit": 0}
    correct = bool(got) and all(c["value"] <= c["limit"]
                                for c in compared.values())
    return {"correct": correct, "compared": compared, "control": ctrl,
            "sampled": r["events"], "tie_events": r["tie_events"],
            "hit_gap": r["hit_gap"]}
