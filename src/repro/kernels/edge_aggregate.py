"""Pallas TPU kernel: masked edge aggregation (segment-sum / segment-mean).

Edge-based GNNs (GatedGCN, GraphSAGE, …) aggregate per-edge messages
into destination nodes: ``out[i] = Σ_{e: dst[e]=i} mask[e] · msg[e]``
(mean divides by the valid in-degree). The reference implementations
lower this as an irregular scatter — the data-dependent part that keeps
message passing off systolic hardware.

HARDWARE ADAPTATION (GPU/FPGA → TPU): the same reformulation that makes
GravNet's kNN gather MXU-native (kernels/gravnet.py) applies to edge
scatter. For a block of ``bm`` destination rows, build the one-hot
incidence slab

    onehot[r, e] = (dst[e] == row_r) · mask[e]          (VPU compare)
    out_block    = onehot @ messages                    (MXU matmul)

so the whole scatter becomes a statically scheduled dense matmul of
shape (bm, E) × (E, d). The mask rides inside the incidence slab, which
reproduces the reference's ``messages * mask`` weighting exactly (and
for mean, ``row_sum(onehot)`` is exactly the reference's masked edge
count). Cost: N·E MACs per feature column — MXU noise at trigger-scale
graphs (N ≤ a few hundred, E ≈ 4N).

Knobs: ``bm`` tiles destination rows per grid step; ``be`` splits the
edge axis into VMEM-bounded chunks accumulated in order (an f32
association knob like fused-dense ``bk`` — a non-default ``be`` must
win on measured time; the default single chunk matches the reference's
one-shot segment reduction up to matmul summation order).

BATCHED FORM: ``edge_aggregate_batched_pallas`` adds a leading event
grid dimension — grid (B, N/bm) — sharing the same cell body, so each
cell sees exactly one event's edge list and aggregation stays
block-diagonal across the micro-batch by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import mxu


def _edge_aggregate_cell(msgs, dst, maskv, i, *, bm, be, reduce, out_dtype):
    """One destination-row block: msgs:(E,d) against dst/maskv:(E,);
    ``i`` is the row-block index within the event. Shared verbatim by
    the per-event and batched kernels."""
    e, d = msgs.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, be), 0) + i * bm
    acc = jnp.zeros((bm, d), jnp.float32)
    cnt = jnp.zeros((bm,), jnp.float32)
    for c in range(e // be):  # static unrolled edge-chunk loop
        mc = msgs[c * be:(c + 1) * be]
        dc = dst[c * be:(c + 1) * be]
        kc = maskv[c * be:(c + 1) * be]
        onehot = ((rows == dc[None, :]).astype(jnp.float32)
                  * kc[None, :])                          # (bm, be)
        acc = acc + mxu.dot(onehot, mc)
        if reduce == "mean":
            cnt = cnt + jnp.sum(onehot, axis=1)
    if reduce == "mean":
        acc = acc / jnp.maximum(cnt, 1.0)[:, None]
    return acc.astype(out_dtype)


def _edge_aggregate_kernel(m_ref, d_ref, k_ref, o_ref, *, bm, be, reduce,
                           out_dtype):
    o_ref[...] = _edge_aggregate_cell(
        m_ref[...].astype(jnp.float32),    # (e, d) all messages
        d_ref[...][:, 0],                  # (e,)   destination ids
        k_ref[...][:, 0],                  # (e,)   edge validity
        pl.program_id(0), bm=bm, be=be, reduce=reduce, out_dtype=out_dtype)


def _edge_aggregate_kernel_batched(m_ref, d_ref, k_ref, o_ref, *, bm, be,
                                   reduce, out_dtype):
    # leading block dim is 1 (one event per grid cell along axis 0);
    # [0] drops it so the cell body is identical to the per-event form
    o_ref[0] = _edge_aggregate_cell(
        m_ref[0].astype(jnp.float32),
        d_ref[0][:, 0],
        k_ref[0][:, 0],
        pl.program_id(1), bm=bm, be=be, reduce=reduce, out_dtype=out_dtype)


def edge_aggregate_pallas(messages, dst, mask, *, n_nodes, reduce="sum",
                          bm=None, be=None, out_dtype=None,
                          interpret=False):
    """Edge aggregation. messages:(E,d), dst:(E,), mask:(E,) ->
    (n_nodes, d). Caller pads n_nodes to a multiple of ``bm`` and E to
    a multiple of ``be``; padded edges carry mask 0."""
    e, d = messages.shape
    out_dtype = out_dtype or messages.dtype
    bm = bm or min(n_nodes, 128)
    be = be or e
    assert n_nodes % bm == 0, (n_nodes, bm)
    assert e % be == 0, (e, be)
    dst2 = dst.reshape(e, 1).astype(jnp.int32)
    mask2 = mask.reshape(e, 1).astype(jnp.float32)
    kern = functools.partial(_edge_aggregate_kernel, bm=bm, be=be,
                             reduce=reduce, out_dtype=out_dtype)
    return pl.pallas_call(
        kern,
        grid=(n_nodes // bm,),
        out_shape=jax.ShapeDtypeStruct((n_nodes, d), out_dtype),
        in_specs=[
            pl.BlockSpec((e, d), lambda i: (0, 0)),    # all messages
            pl.BlockSpec((e, 1), lambda i: (0, 0)),    # destinations
            pl.BlockSpec((e, 1), lambda i: (0, 0)),    # edge mask
        ],
        out_specs=pl.BlockSpec((bm, d), lambda i: (i, 0)),
        interpret=interpret,
    )(messages, dst2, mask2)


def edge_aggregate_batched_pallas(messages, dst, mask, *, n_nodes,
                                  reduce="sum", bm=None, be=None,
                                  out_dtype=None, interpret=False):
    """Micro-batched edge aggregation in ONE kernel launch.

    messages:(B,E,d), dst:(B,E), mask:(B,E) -> (B, n_nodes, d). Grid is
    (B, N/bm): the leading grid dimension walks events, so each cell
    sees exactly one event's edge list — no cross-event edge can form.
    """
    b, e, d = messages.shape
    out_dtype = out_dtype or messages.dtype
    bm = bm or min(n_nodes, 128)
    be = be or e
    assert n_nodes % bm == 0, (n_nodes, bm)
    assert e % be == 0, (e, be)
    dst2 = dst.reshape(b, e, 1).astype(jnp.int32)
    mask2 = mask.reshape(b, e, 1).astype(jnp.float32)
    kern = functools.partial(_edge_aggregate_kernel_batched, bm=bm, be=be,
                             reduce=reduce, out_dtype=out_dtype)
    return pl.pallas_call(
        kern,
        grid=(b, n_nodes // bm),
        out_shape=jax.ShapeDtypeStruct((b, n_nodes, d), out_dtype),
        in_specs=[
            pl.BlockSpec((1, e, d), lambda ev, i: (ev, 0, 0)),
            pl.BlockSpec((1, e, 1), lambda ev, i: (ev, 0, 0)),
            pl.BlockSpec((1, e, 1), lambda ev, i: (ev, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, d), lambda ev, i: (ev, i, 0)),
        interpret=interpret,
    )(messages, dst2, mask2)
