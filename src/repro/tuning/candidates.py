"""Candidate launch configurations for the tunable Pallas kernels.

The search spaces mirror the knobs the kernels actually expose:

- ``fused_dense``: the looped/flattened variant split (the paper's
  loop-pipelined vs ``chess_flatten_loop`` study) and the looped
  variant's ``(bm, bn, bk)`` block shapes;
- ``gravnet``: the row-tile ``bm`` (how many query rows per grid step
  share the VMEM-resident coordinate/feature operands);
- ``flash_attention``: the ``(bq, bk)`` q/kv block shapes.

Every candidate list starts with the **heuristic default** the code
would pick without tuning; the autotuner only switches away from it on
a measured, above-noise win, so an unlucky timing run can never make
things worse than today's behavior.

Fused-dense blocks must compile on the TPU, whose lowering takes a
block's last two dims only as multiples of (8, 128) or as the whole
(padded) array dim. The kernels' wrappers pad operands to block
multiples, so the searched blocks are powers of two of at least 8 rows
and 128 lanes, and the default spans a dim narrower than one tile
(``kernel_opt.fused_dense_default``).
"""
from __future__ import annotations

from repro.core.passes import kernel_opt as _ko


def _pow2_range(lo: int, hi: int) -> list[int]:
    out = []
    v = lo
    while v <= hi:
        out.append(v)
        v *= 2
    return out


def _dedup_keep_order(cands: list[dict]) -> list[dict]:
    seen, out = set(), []
    for c in cands:
        sig = tuple(sorted(c.items()))
        if sig not in seen:
            seen.add(sig)
            out.append(c)
    return out


def default_fused_dense(rows: int, d_in: int, d_out: int) -> dict:
    """The untuned heuristic from ``kernel_opt`` (kept in one place so
    the bit-for-bit fallback and the search baseline cannot drift)."""
    return _ko.fused_dense_default(rows, d_in, d_out)


def fused_dense_candidates(rows: int, d_in: int, d_out: int,
                           *, max_candidates: int = 16) -> list[dict]:
    cands = [default_fused_dense(rows, d_in, d_out)]
    # the flattened variant is only launchable when the whole operand
    # set fits VMEM comfortably; use the kernel_opt envelope ×2 so the
    # search can discover wins just past the heuristic cliff
    if rows <= 2 * _ko.FLATTEN_ROWS and max(d_in, d_out) <= _ko.FLATTEN_DIM:
        cands.append({"variant": "flattened"})
    bm_opts = [b for b in _pow2_range(8, 512) if b <= max(rows, 8)]
    bn_opts = [b for b in _pow2_range(128, 512) if b <= max(d_out, 128)]
    bk_opts = [b for b in _pow2_range(128, 2048) if b <= max(d_in, 128)]
    for bm in reversed(bm_opts[-3:]):        # largest row tiles first
        for bn in reversed(bn_opts[-2:]):
            for bk in reversed(bk_opts[-2:]):
                cands.append({"variant": "looped",
                              "bm": bm, "bn": bn, "bk": bk})
    return _dedup_keep_order(cands)[:max_candidates]


def default_fused_dense_int8(rows: int, d_in: int, d_out: int) -> dict:
    """The int8 executor path has no flattened variant; untuned it runs
    the looped kernel at the wrapper's default blocks."""
    return {"variant": "looped", "bm": 128, "bn": 128, "bk": 512}


def fused_dense_int8_candidates(rows: int, d_in: int, d_out: int,
                                *, max_candidates: int = 16) -> list[dict]:
    cands = [default_fused_dense_int8(rows, d_in, d_out)]
    cands += [c for c in fused_dense_candidates(rows, d_in, d_out)
              if c.get("variant") == "looped"]
    return _dedup_keep_order(cands)[:max_candidates]


def default_gravnet(n: int, batch: int = 1) -> dict:
    """The row-tile heuristic is per-event, so it is batch-invariant:
    the batched kernel's leading event grid dimension changes how many
    cells launch, not the cell's block shape."""
    return {"bm": min(n, 128)}


def gravnet_candidates(n: int, *, batch: int = 1,
                       max_candidates: int = 8) -> list[dict]:
    cands = [default_gravnet(n, batch)]
    for bm in _pow2_range(8, 512):
        if n % bm == 0:        # the kernel asserts n % bm == 0
            cands.append({"bm": bm})
    return _dedup_keep_order(cands)[:max_candidates]


def default_gravnet_block(n: int, batch: int = 1) -> dict:
    """Heuristic default for the fused block: the aggregation row tile
    (shared with the standalone gravnet kernel — batch-invariant) and a
    whole-operand epilogue (no bn/bk splits), which is the bitwise-safe
    configuration the executor uses on a cache miss."""
    return {"bm": min(n, 128)}


def gravnet_block_candidates(n: int, d_hidden: int, d_f: int, d_out: int,
                             *, concat_x: bool = True, batch: int = 1,
                             max_candidates: int = 10) -> list[dict]:
    """Search space for the megakernel: the row tile ``bm`` plus the
    epilogue's ``(bn, bk)`` blocking. ``bn`` splits output columns
    (bitwise-neutral); ``bk`` splits the epilogue K reduction (last-ulp
    f32 association may differ — it must win on measured time)."""
    cands = [default_gravnet_block(n, batch)]
    for bm in _pow2_range(8, 512):
        if n % bm == 0:        # the kernel asserts n % bm == 0
            cands.append({"bm": bm})
    bm0 = default_gravnet_block(n, batch)["bm"]
    dcat = d_hidden + 2 * d_f if concat_x else 2 * d_f
    for bn in _pow2_range(32, 256):
        if bn < d_out:
            cands.append({"bm": bm0, "bn": bn})
    for bk in _pow2_range(32, 256):
        if bk < dcat:
            cands.append({"bm": bm0, "bk": bk})
    return _dedup_keep_order(cands)[:max_candidates]


def default_gravnet_block_int8(n: int, batch: int = 1) -> dict:
    """Heuristic default for the quantized block: identical launch
    surface to the f32 megakernel (same row tile, whole-operand
    epilogue), so the untuned int8 binding mirrors the untuned f32
    one."""
    return {"bm": min(n, 128)}


def gravnet_block_int8_candidates(n: int, d_hidden: int, d_f: int,
                                  d_out: int, *, concat_x: bool = True,
                                  batch: int = 1,
                                  max_candidates: int = 10) -> list[dict]:
    """Search space for the quantized megakernel — the same (bm, bn,
    bk) knobs as the f32 block, searched under its own dtype-tagged
    key. One numerics difference widens the usable space: the epilogue
    accumulates in int32, so even ``bk`` K-splits are *exact* (no
    last-ulp caveat), and any measured winner is safe to bind."""
    cands = [default_gravnet_block_int8(n, batch)]
    for bm in _pow2_range(8, 512):
        if n % bm == 0:        # the kernel asserts n % bm == 0
            cands.append({"bm": bm})
    bm0 = default_gravnet_block_int8(n, batch)["bm"]
    dcat = d_hidden + 2 * d_f if concat_x else 2 * d_f
    for bn in _pow2_range(32, 256):
        if bn < d_out:
            cands.append({"bm": bm0, "bn": bn})
    for bk in _pow2_range(32, 256):
        if bk < dcat:
            cands.append({"bm": bm0, "bk": bk})
    return _dedup_keep_order(cands)[:max_candidates]


def default_edge_aggregate(n: int, e: int, batch: int = 1) -> dict:
    """Heuristic default for the edge-aggregation kernel: the gravnet
    row-tile rule (batch-invariant) and a single whole-edge-set chunk —
    the configuration the executor uses on a cache miss."""
    return {"bm": min(n, 128)}


def edge_aggregate_candidates(n: int, e: int, *, batch: int = 1,
                              max_candidates: int = 10) -> list[dict]:
    """Search space: the destination row tile ``bm`` plus the edge-axis
    chunk ``be``. ``be`` splits the f32 accumulation into ordered
    chunks (association may move last ulps — it must win on measured
    time, like fused-dense ``bk``)."""
    cands = [default_edge_aggregate(n, e, batch)]
    for bm in _pow2_range(8, 512):
        if n % bm == 0:        # the kernel asserts n % bm == 0
            cands.append({"bm": bm})
    bm0 = default_edge_aggregate(n, e, batch)["bm"]
    for be in _pow2_range(128, 2048):
        if be < e and e % be == 0:   # the kernel asserts e % be == 0
            cands.append({"bm": bm0, "be": be})
    return _dedup_keep_order(cands)[:max_candidates]


def default_knn_build(n: int, batch: int = 1) -> dict:
    """Heuristic default for the ragged kNN kernels: the gravnet
    row-tile rule (batch-invariant — the batched form only adds a
    leading bin grid dimension)."""
    return {"bm": min(n, 128)}


def knn_build_candidates(n: int, *, batch: int = 1,
                         max_candidates: int = 8) -> list[dict]:
    cands = [default_knn_build(n, batch)]
    for bm in _pow2_range(8, 512):
        if n % bm == 0:        # the kernel asserts n % bm == 0
            cands.append({"bm": bm})
    return _dedup_keep_order(cands)[:max_candidates]


def default_knn_aggregate(n: int, batch: int = 1) -> dict:
    return {"bm": min(n, 128)}


def knn_aggregate_candidates(n: int, *, batch: int = 1,
                             max_candidates: int = 8) -> list[dict]:
    cands = [default_knn_aggregate(n, batch)]
    for bm in _pow2_range(8, 512):
        if n % bm == 0:        # the kernel asserts n % bm == 0
            cands.append({"bm": bm})
    return _dedup_keep_order(cands)[:max_candidates]


def default_flash_attention() -> dict:
    return {"bq": 128, "bk": 128}


def flash_attention_candidates(s: int, t: int,
                               *, max_candidates: int = 8) -> list[dict]:
    cands = [default_flash_attention()]
    for bq in _pow2_range(64, 256):
        for bk in _pow2_range(64, 256):
            cands.append({"bq": min(bq, s), "bk": min(bk, t)})
    return _dedup_keep_order(cands)[:max_candidates]
