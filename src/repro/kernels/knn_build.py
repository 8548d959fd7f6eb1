"""Pallas TPU kernels: on-device kNN graph building for ragged events.

The bucketed path pads every event to its bucket's hit count and lets
``kernels/gravnet.py`` fuse selection and aggregation per event. The
ragged path instead bin-packs *whole events* into fixed ``capacity``-row
bins (``data/ragged.py``) and splits GravNet into two kernels:

  **knn_build**     — neighbor *selection* in the learned coordinate
                      space: per packed row, the k nearest same-event
                      rows (iterated row-argmin with knockout — the
                      same MXU-friendly schedule as the gravnet
                      kernel), emitting neighbor indices + squared
                      distances. Segment ids replace the validity
                      mask: a candidate column is valid iff it carries
                      the *same event id* as the row and is not the
                      row itself, so selection stays block-diagonal
                      per event even when several events share a bin
                      (pad rows carry segid −1 and match nothing).
  **knn_aggregate** — Gaussian-potential mean/max aggregation of the
                      learned features over those indices, via one-hot
                      matmul (MXU), reproducing ``_gravnet_cell``'s
                      arithmetic bit-for-bit.

TIE-BREAK CONTRACT (pinned by tests/test_knn_build.py): at each of the
k selection steps the *lowest column index* among the minimal
distances wins (``jnp.argmin`` semantics), then the winner is knocked
out. Because bin packing keeps an event's hits contiguous and
in-order, within-event relative column order — and therefore every
tie-break — is identical to the padded per-event launch, which is what
makes ragged and padded outputs bitwise-equal in f32 on real rows
(tested). Rows with fewer than k same-event candidates pad their
remaining slots with distance ``1e30``; the aggregate weighs those
slots 0 (exactly the gravnet kernel's exhausted-candidate behavior).

Grid/blocking mirrors kernels/gravnet.py: rows are tiled ``bm`` per
step with the full per-bin operands VMEM-resident; the batched forms
add a leading bin/event grid dimension with block size 1, so one
launch serves the whole packed micro-batch. Cell bodies are shared
verbatim between the per-bin and batched kernels (batched-vs-looped is
bitwise, tested).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import mxu


def _knn_select_cell(si, sj, segi, segj, i, *, k, bm):
    """One row-block of neighbor selection: si:(bm,ds) rows against
    sj:(n,ds) candidates with segment ids segi:(bm,)/segj:(n,).
    Returns (idx:(bm,k) i32, d2:(bm,k) f32). Shared verbatim by the
    per-bin and batched kernels."""
    n = sj.shape[0]
    d2 = (jnp.sum(si * si, axis=1, keepdims=True)
          + jnp.sum(sj * sj, axis=1)[None, :]
          - 2.0 * mxu.dot(si, sj.T))
    col = jax.lax.broadcasted_iota(jnp.int32, (bm, n), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (bm, n), 0) + i * bm
    # same-event candidates only; exclude self and padding (segid < 0)
    invalid = ((segj[None, :] != segi[:, None]) | (col == row)
               | (segj[None, :] < 0))
    big = jnp.float32(1e30)
    d2 = jnp.where(invalid, big, jnp.maximum(d2, 0.0))

    kcol = jax.lax.broadcasted_iota(jnp.int32, (bm, k), 1)
    idx_acc = jnp.zeros((bm, k), jnp.int32)
    d2_acc = jnp.full((bm, k), big, jnp.float32)

    def body(t, carry):
        d2, idx_acc, d2_acc = carry
        dmin = jnp.min(d2, axis=1)                          # (bm,)
        amin = jnp.argmin(d2, axis=1).astype(jnp.int32)     # ties -> lowest
        idx_acc = jnp.where(kcol == t, amin[:, None], idx_acc)
        d2_acc = jnp.where(kcol == t, dmin[:, None], d2_acc)
        d2 = jnp.where(col == amin[:, None], big, d2)       # knockout
        return d2, idx_acc, d2_acc

    _, idx_acc, d2_acc = jax.lax.fori_loop(0, k, body,
                                           (d2, idx_acc, d2_acc))
    return idx_acc, d2_acc


def _knn_agg_cell(fj, idx, d2, *, k, scale, bm, out_dtype):
    """One row-block of Gaussian-potential aggregation over selected
    neighbors: fj:(n,df) features, idx/d2:(bm,k) from the selection
    cell. One-hot matmul per step — the same accumulation schedule as
    ``gravnet._gravnet_cell``, hence bitwise-equal in f32 when fed
    that kernel's selection order."""
    n, df = fj.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (bm, n), 1)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (bm, k), 1)
    big = jnp.float32(1e30)
    mean_acc = jnp.zeros((bm, df), jnp.float32)
    max_acc = jnp.full((bm, df), -big, jnp.float32)

    def body(t, carry):
        mean_acc, max_acc = carry
        sel = kcol == t
        amin = jnp.sum(jnp.where(sel, idx, 0), axis=1)       # (bm,)
        dmin = jnp.sum(jnp.where(sel, d2, 0.0), axis=1)      # (bm,)
        onehot = (col == amin[:, None]).astype(jnp.float32)  # (bm, n)
        fsel = mxu.dot(onehot, fj)
        valid = dmin < big * 0.5
        w = jnp.where(valid, jnp.exp(-scale * dmin), 0.0)
        wf = w[:, None] * fsel
        mean_acc = mean_acc + wf
        max_acc = jnp.maximum(max_acc,
                              jnp.where(valid[:, None], wf, -big))
        return mean_acc, max_acc

    mean_acc, max_acc = jax.lax.fori_loop(0, k, body, (mean_acc, max_acc))
    mean = mean_acc / jnp.float32(k)
    maxv = jnp.where(max_acc <= -big * 0.5, 0.0, max_acc)
    return jnp.concatenate([mean, maxv], axis=1).astype(out_dtype)


# ------------------------------------------------------- selection kernels ----
def _knn_build_kernel(si_ref, s_ref, segi_ref, seg_ref, idx_ref, d2_ref,
                      *, k, bm):
    idx, d2 = _knn_select_cell(
        si_ref[...].astype(jnp.float32),       # (bm, ds) row block
        s_ref[...].astype(jnp.float32),        # (n, ds)  all coords
        segi_ref[...][:, 0],                   # (bm,)    row segids
        seg_ref[...][:, 0],                    # (n,)     all segids
        pl.program_id(0), k=k, bm=bm)
    idx_ref[...] = idx
    d2_ref[...] = d2


def _knn_build_kernel_batched(si_ref, s_ref, segi_ref, seg_ref, idx_ref,
                              d2_ref, *, k, bm):
    # leading block dim is 1 (one bin per grid cell along axis 0)
    idx, d2 = _knn_select_cell(
        si_ref[0].astype(jnp.float32),
        s_ref[0].astype(jnp.float32),
        segi_ref[0][:, 0],
        seg_ref[0][:, 0],
        pl.program_id(1), k=k, bm=bm)
    idx_ref[0] = idx
    d2_ref[0] = d2


def knn_build_pallas(s, segids, *, k=8, bm=None, interpret=False):
    """Neighbor selection for one packed bin. s:(N,ds), segids:(N,) i32
    -> (idx:(N,k) i32, d2:(N,k) f32). Caller pads N to a multiple of
    ``bm``; padding rows carry segid −1 and select nothing."""
    n, ds = s.shape
    bm = bm or min(n, 128)
    assert n % bm == 0, (n, bm)
    seg2 = segids.reshape(n, 1).astype(jnp.int32)
    kern = functools.partial(_knn_build_kernel, k=k, bm=bm)
    return pl.pallas_call(
        kern,
        grid=(n // bm,),
        out_shape=(jax.ShapeDtypeStruct((n, k), jnp.int32),
                   jax.ShapeDtypeStruct((n, k), jnp.float32)),
        in_specs=[
            pl.BlockSpec((bm, ds), lambda i: (i, 0)),   # row block
            pl.BlockSpec((n, ds), lambda i: (0, 0)),    # all coords
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),    # row segids
            pl.BlockSpec((n, 1), lambda i: (0, 0)),     # all segids
        ],
        out_specs=(pl.BlockSpec((bm, k), lambda i: (i, 0)),
                   pl.BlockSpec((bm, k), lambda i: (i, 0))),
        interpret=interpret,
    )(s, s, seg2, seg2)


def knn_build_batched_pallas(s, segids, *, k=8, bm=None, interpret=False):
    """Batched neighbor selection in ONE launch. s:(B,N,ds),
    segids:(B,N) -> (idx:(B,N,k), d2:(B,N,k)). Grid (B, N/bm); each
    cell sees one bin's operands (same cell body as the per-bin form,
    so batched-vs-looped is bitwise)."""
    b, n, ds = s.shape
    bm = bm or min(n, 128)
    assert n % bm == 0, (n, bm)
    seg2 = segids.reshape(b, n, 1).astype(jnp.int32)
    kern = functools.partial(_knn_build_kernel_batched, k=k, bm=bm)
    return pl.pallas_call(
        kern,
        grid=(b, n // bm),
        out_shape=(jax.ShapeDtypeStruct((b, n, k), jnp.int32),
                   jax.ShapeDtypeStruct((b, n, k), jnp.float32)),
        in_specs=[
            pl.BlockSpec((1, bm, ds), lambda e, i: (e, i, 0)),
            pl.BlockSpec((1, n, ds), lambda e, i: (e, 0, 0)),
            pl.BlockSpec((1, bm, 1), lambda e, i: (e, i, 0)),
            pl.BlockSpec((1, n, 1), lambda e, i: (e, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((1, bm, k), lambda e, i: (e, i, 0)),
                   pl.BlockSpec((1, bm, k), lambda e, i: (e, i, 0))),
        interpret=interpret,
    )(s, s, seg2, seg2)


# ----------------------------------------------------- aggregation kernels ----
def _knn_agg_kernel(f_ref, idx_ref, d2_ref, o_ref, *, k, scale, bm,
                    out_dtype):
    o_ref[...] = _knn_agg_cell(
        f_ref[...].astype(jnp.float32),        # (n, df) all features
        idx_ref[...],                          # (bm, k) neighbor ids
        d2_ref[...].astype(jnp.float32),       # (bm, k) distances
        k=k, scale=scale, bm=bm, out_dtype=out_dtype)


def _knn_agg_kernel_batched(f_ref, idx_ref, d2_ref, o_ref, *, k, scale,
                            bm, out_dtype):
    o_ref[0] = _knn_agg_cell(
        f_ref[0].astype(jnp.float32),
        idx_ref[0],
        d2_ref[0].astype(jnp.float32),
        k=k, scale=scale, bm=bm, out_dtype=out_dtype)


def knn_aggregate_pallas(f, idx, d2, *, scale=10.0, bm=None, out_dtype=None,
                         interpret=False):
    """Aggregate one packed bin. f:(N,df), idx/d2:(N,k) -> (N, 2·df)."""
    n, df = f.shape
    k = idx.shape[1]
    out_dtype = out_dtype or f.dtype
    bm = bm or min(n, 128)
    assert n % bm == 0, (n, bm)
    kern = functools.partial(_knn_agg_kernel, k=k, scale=scale, bm=bm,
                             out_dtype=out_dtype)
    return pl.pallas_call(
        kern,
        grid=(n // bm,),
        out_shape=jax.ShapeDtypeStruct((n, 2 * df), out_dtype),
        in_specs=[
            pl.BlockSpec((n, df), lambda i: (0, 0)),    # all features
            pl.BlockSpec((bm, k), lambda i: (i, 0)),    # row indices
            pl.BlockSpec((bm, k), lambda i: (i, 0)),    # row distances
        ],
        out_specs=pl.BlockSpec((bm, 2 * df), lambda i: (i, 0)),
        interpret=interpret,
    )(f, idx, d2)


def knn_aggregate_batched_pallas(f, idx, d2, *, scale=10.0, bm=None,
                                 out_dtype=None, interpret=False):
    """Batched aggregation in ONE launch. f:(B,N,df), idx/d2:(B,N,k)
    -> (B, N, 2·df). Grid (B, N/bm), shared cell body."""
    b, n, df = f.shape
    k = idx.shape[2]
    out_dtype = out_dtype or f.dtype
    bm = bm or min(n, 128)
    assert n % bm == 0, (n, bm)
    kern = functools.partial(_knn_agg_kernel_batched, k=k, scale=scale,
                             bm=bm, out_dtype=out_dtype)
    return pl.pallas_call(
        kern,
        grid=(b, n // bm),
        out_shape=jax.ShapeDtypeStruct((b, n, 2 * df), out_dtype),
        in_specs=[
            pl.BlockSpec((1, n, df), lambda e, i: (e, 0, 0)),
            pl.BlockSpec((1, bm, k), lambda e, i: (e, i, 0)),
            pl.BlockSpec((1, bm, k), lambda e, i: (e, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, 2 * df), lambda e, i: (e, i, 0)),
        interpret=interpret,
    )(f, idx, d2)
