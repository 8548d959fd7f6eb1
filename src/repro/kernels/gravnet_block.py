"""Pallas TPU megakernel: one whole GravNet block per launch.

The deployed CaloClusterNet spends its latency budget in the GravNet
blocks, yet the unfused executor runs each block as 3–4 separate
launches — S/F projection dense(s), ``gravnet_aggregate``, and the
post-aggregation dense — materializing every intermediate to HBM
between them. LL-GNN (arXiv:2209.14065) shows that collapsing GNN
layer boundaries is the key to sub-microsecond latency; this kernel
applies the same move on TPU by fusing

    dense(S-proj) ∥ dense(F-proj) → k-NN aggregate → dense(out)+act

into ONE ``pallas_call``:

- **prologue** — the S/F projections run as matmuls on the
  VMEM-resident ``x`` operand: ``S = x @ Ws + bs`` (per row block AND
  for the full event, since every query block aggregates against all
  nodes) and ``F = x @ Wf + bf``. Neither S nor F ever reaches HBM.
- **body** — the k-NN aggregation reuses ``gravnet._gravnet_cell``
  *verbatim* (same argmin/one-hot/matmul schedule, same row tile
  ``bm``), so the aggregation is bitwise-identical in f32 to the
  standalone gravnet kernel at the same ``bm``.
- **epilogue** — the output dense consumes ``concat(x_block, agg)``
  (``concat_x=True``, the CaloClusterNet shape) or ``agg`` alone, adds
  the bias, applies the activation, and writes the only HBM output.
  Optional ``(bn, bk)`` blocking tiles the epilogue matmul for the
  autotuner; the defaults run one whole-operand dot, which keeps the
  fused output bitwise-equal (f32) to the unfused chain (tested).

BATCHED (occupancy-bucketed) FORM: ``gravnet_block_batched_pallas``
adds the same leading *event* grid dimension as the batched gravnet
kernel — grid ``(B, N/bm)`` — so one launch serves a whole serving
micro-batch. Each cell sees exactly one event's operands (weights are
shared across the event grid; their BlockSpecs ignore the indices), so
aggregation stays block-diagonal by construction.

The S/F prologue is recomputed per row block when ``bm < N`` (every
query block needs all N projected rows). At trigger scale that trade
is free — the recomputed matmuls are (N, d_hidden) @ (d_hidden, d_s/f)
with d_s ≤ 4, d_f ≤ 32 — and it is what keeps the kernel free of
cross-grid-step communication.

QUANTIZED (int8) FORM: ``gravnet_block_int8_pallas`` /
``gravnet_block_int8_batched_pallas`` run the same schedule in the
mixed-precision interior's arithmetic, with the three calibrated
per-tensor activation scales baked in as kernel *constants* (python
floats closed over at trace time — no scalar operands to fetch):

- the f32 input rows quantize to int8 in VMEM with ``x_scale`` (the
  producer's calibrated activation scale), exactly as the unfused
  calibrated dense does on entry;
- the S/F prologue runs int8×int8→int32 MXU dots, dequantized through
  ``x_scale · w_scale[col]`` (+bias) to f32 — the unfused chain never
  requantizes S/F (the merged projection's output feeds retile/slice
  views, which break the int8 emit chain), so neither does the kernel;
- the aggregation body is the same f32 ``_gravnet_cell``; its output
  snaps to the int8 grid via ``agg_scale`` (the aggregate op's
  calibrated activation scale), modeling 8-bit fabric arithmetic;
- the epilogue quantizes ``concat(x, agg)`` with ``h_scale`` in VMEM
  and runs the output dense as int8×int8→int32 dots (the (bn, bk)
  epilogue blocking stays available — int32 partial sums make even the
  ``bk`` K-split *exact*, unlike the f32 epilogue), dequantizing
  through ``h_scale · wo_scale[col]`` + bias + activation. The only
  HBM write is the final f32 (or requantized int8) output.

Everything between the HBM read of x and the HBM write of y — both
quantize steps, three int8 matmuls, the aggregation, the requant snap
— lives in VMEM/registers for the grid cell's lifetime.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import mxu
from repro.kernels.fused_dense import _activate
from repro.kernels.gravnet import _gravnet_cell


def _epilogue_dense(h, wo, bo, *, bn, bk, activation, out_dtype):
    """act(h @ wo + bo) with optional (bn, bk) epilogue blocking.

    Defaults (bn=bk=None) run one whole-operand dot — bitwise identical
    to the unfused fused_dense kernel's matmul. ``bn`` splits output
    columns (still bitwise: column decomposition leaves each element's
    K reduction intact); ``bk`` splits the K reduction itself, whose
    f32 partial-sum association may differ in the last ulp — it is an
    autotuner-only option that must win on measured time to bind.
    """
    dcat, dout = wo.shape
    bn = dout if bn is None else min(bn, dout)
    bk = dcat if bk is None else min(bk, dcat)
    cols = []
    for j0 in range(0, dout, bn):
        j1 = min(j0 + bn, dout)
        parts = [mxu.dot(h[:, k0:min(k0 + bk, dcat)],
                         wo[k0:min(k0 + bk, dcat), j0:j1])
                 for k0 in range(0, dcat, bk)]
        acc = parts[0]
        for p in parts[1:]:
            acc = acc + p
        cols.append(acc)
    y = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
    y = y + bo.astype(jnp.float32)
    return _activate(y, activation).astype(out_dtype)


def _gravnet_block_cell(xi, xall, maskj, ws, bs, wf, bf, wo, bo, i, *, k,
                        scale, bm, bn, bk, activation, concat_x, out_dtype):
    """One row block of one event, prologue → aggregate → epilogue.

    xi:(bm,dh) query rows, xall:(n,dh) all rows, maskj:(n,) validity;
    ``i`` is the row-block index within the event. All arithmetic f32.
    """
    s_all = mxu.dot(xall, ws) + bs.astype(jnp.float32)
    f_all = mxu.dot(xall, wf) + bf.astype(jnp.float32)
    # the query rows' coordinates: recomputed from the row block (f32
    # matmul rows are independent, so this equals s_all's rows bitwise)
    si = mxu.dot(xi, ws) + bs.astype(jnp.float32)
    agg = _gravnet_cell(si, s_all, f_all, maskj, i, k=k, scale=scale,
                        bm=bm, out_dtype=jnp.float32)
    h = jnp.concatenate([xi, agg], axis=1) if concat_x else agg
    return _epilogue_dense(h, wo, bo, bn=bn, bk=bk, activation=activation,
                           out_dtype=out_dtype)


def _gravnet_block_kernel(xi_ref, x_ref, mask_ref, ws_ref, bs_ref, wf_ref,
                          bf_ref, wo_ref, bo_ref, o_ref, *, k, scale, bm,
                          bn, bk, activation, concat_x, out_dtype):
    o_ref[...] = _gravnet_block_cell(
        xi_ref[...].astype(jnp.float32),       # (bm, dh) query rows
        x_ref[...].astype(jnp.float32),        # (n, dh)  all rows
        mask_ref[...][:, 0],                   # (n,)     validity
        ws_ref[...].astype(jnp.float32), bs_ref[...],
        wf_ref[...].astype(jnp.float32), bf_ref[...],
        wo_ref[...].astype(jnp.float32), bo_ref[...],
        pl.program_id(0), k=k, scale=scale, bm=bm, bn=bn, bk=bk,
        activation=activation, concat_x=concat_x, out_dtype=out_dtype)


def _gravnet_block_kernel_batched(xi_ref, x_ref, mask_ref, ws_ref, bs_ref,
                                  wf_ref, bf_ref, wo_ref, bo_ref, o_ref, *,
                                  k, scale, bm, bn, bk, activation,
                                  concat_x, out_dtype):
    # leading block dim is 1 (one event per grid cell along axis 0);
    # [0] drops it so the cell body is identical to the per-event form
    o_ref[0] = _gravnet_block_cell(
        xi_ref[0].astype(jnp.float32),
        x_ref[0].astype(jnp.float32),
        mask_ref[0][:, 0],
        ws_ref[...].astype(jnp.float32), bs_ref[...],
        wf_ref[...].astype(jnp.float32), bf_ref[...],
        wo_ref[...].astype(jnp.float32), bo_ref[...],
        pl.program_id(1), k=k, scale=scale, bm=bm, bn=bn, bk=bk,
        activation=activation, concat_x=concat_x, out_dtype=out_dtype)


def gravnet_block_pallas(x, mask, ws, bs, wf, bf, wo, bo, *, k=8,
                         scale=10.0, activation="relu", concat_x=True,
                         bm=None, bn=None, bk=None, out_dtype=None,
                         interpret=False):
    """One GravNet block, one launch. x:(N,dh) mask:(N,) -> (N, d_out).

    ws:(dh,ds)/bs:(ds,) and wf:(dh,df)/bf:(df,) are the S/F projection
    params; wo:(dh+2·df, d_out) (or (2·df, d_out) with concat_x=False)
    and bo:(d_out,) the output dense. Caller pads N to a multiple of
    ``bm`` (``ops.gravnet_block`` does).
    """
    n, dh = x.shape
    ds, df = ws.shape[1], wf.shape[1]
    dcat, dout = wo.shape
    out_dtype = out_dtype or x.dtype
    bm = bm or min(n, 128)
    assert n % bm == 0, (n, bm)
    assert dcat == (dh + 2 * df if concat_x else 2 * df), (dcat, dh, df)
    mask2 = mask.reshape(n, 1).astype(jnp.float32)
    bs2, bf2, bo2 = (bs.reshape(1, ds), bf.reshape(1, df),
                     bo.reshape(1, dout))
    kern = functools.partial(_gravnet_block_kernel, k=k, scale=scale, bm=bm,
                             bn=bn, bk=bk, activation=activation,
                             concat_x=concat_x, out_dtype=out_dtype)
    return pl.pallas_call(
        kern,
        grid=(n // bm,),
        out_shape=jax.ShapeDtypeStruct((n, dout), out_dtype),
        in_specs=[
            pl.BlockSpec((bm, dh), lambda i: (i, 0)),      # query rows
            pl.BlockSpec((n, dh), lambda i: (0, 0)),       # all rows
            pl.BlockSpec((n, 1), lambda i: (0, 0)),        # mask
            pl.BlockSpec((dh, ds), lambda i: (0, 0)),      # Ws
            pl.BlockSpec((1, ds), lambda i: (0, 0)),       # bs
            pl.BlockSpec((dh, df), lambda i: (0, 0)),      # Wf
            pl.BlockSpec((1, df), lambda i: (0, 0)),       # bf
            pl.BlockSpec((dcat, dout), lambda i: (0, 0)),  # Wo
            pl.BlockSpec((1, dout), lambda i: (0, 0)),     # bo
        ],
        out_specs=pl.BlockSpec((bm, dout), lambda i: (i, 0)),
        interpret=interpret,
    )(x, x, mask2, ws, bs2, wf, bf2, wo, bo2)


def gravnet_block_batched_pallas(x, mask, ws, bs, wf, bf, wo, bo, *, k=8,
                                 scale=10.0, activation="relu",
                                 concat_x=True, bm=None, bn=None, bk=None,
                                 out_dtype=None, interpret=False):
    """Micro-batched GravNet block in ONE kernel launch.

    x:(B,N,dh) mask:(B,N) -> (B, N, d_out). Grid is (B, N/bm): the
    leading grid dimension walks events (weights shared across cells),
    so the whole micro-batch amortizes a single launch while every
    cell sees exactly one event's operands. f32 results are bitwise
    identical to B per-event launches (same cell body, same schedule).
    """
    b, n, dh = x.shape
    ds, df = ws.shape[1], wf.shape[1]
    dcat, dout = wo.shape
    out_dtype = out_dtype or x.dtype
    bm = bm or min(n, 128)
    assert n % bm == 0, (n, bm)
    assert dcat == (dh + 2 * df if concat_x else 2 * df), (dcat, dh, df)
    mask2 = mask.reshape(b, n, 1).astype(jnp.float32)
    bs2, bf2, bo2 = (bs.reshape(1, ds), bf.reshape(1, df),
                     bo.reshape(1, dout))
    kern = functools.partial(_gravnet_block_kernel_batched, k=k,
                             scale=scale, bm=bm, bn=bn, bk=bk,
                             activation=activation, concat_x=concat_x,
                             out_dtype=out_dtype)
    return pl.pallas_call(
        kern,
        grid=(b, n // bm),
        out_shape=jax.ShapeDtypeStruct((b, n, dout), out_dtype),
        in_specs=[
            pl.BlockSpec((1, bm, dh), lambda e, i: (e, i, 0)),   # queries
            pl.BlockSpec((1, n, dh), lambda e, i: (e, 0, 0)),    # all rows
            pl.BlockSpec((1, n, 1), lambda e, i: (e, 0, 0)),     # mask
            pl.BlockSpec((dh, ds), lambda e, i: (0, 0)),         # Ws
            pl.BlockSpec((1, ds), lambda e, i: (0, 0)),          # bs
            pl.BlockSpec((dh, df), lambda e, i: (0, 0)),         # Wf
            pl.BlockSpec((1, df), lambda e, i: (0, 0)),          # bf
            pl.BlockSpec((dcat, dout), lambda e, i: (0, 0)),     # Wo
            pl.BlockSpec((1, dout), lambda e, i: (0, 0)),        # bo
        ],
        out_specs=pl.BlockSpec((1, bm, dout), lambda e, i: (e, i, 0)),
        interpret=interpret,
    )(x, x, mask2, ws, bs2, wf, bf2, wo, bo2)


# ------------------------------------------------------------- int8 form ----
def _quant_act(v, scale):
    """f32 activations → int8 on the calibrated grid (symmetric,
    saturating at ±127) — the same snap the unfused calibrated dense
    applies on entry. ``scale`` is a baked python float."""
    return jnp.clip(jnp.round(v / scale), -127.0, 127.0).astype(jnp.int8)


def _int8_proj(xq, w_q, w_scale, b, x_scale):
    """int8×int8→int32 MXU dot, dequantized per output channel:
    ``acc · (x_scale · w_scale[col]) + b`` in f32. Same expression
    order as the unfused int8 dense kernel's epilogue, so the f32
    results agree bitwise (the int32 accumulation is exact)."""
    acc = jax.lax.dot_general(xq, w_q, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    scale = x_scale * w_scale.astype(jnp.float32)       # (1, d)
    return acc.astype(jnp.float32) * scale + b.astype(jnp.float32)


def _epilogue_dense_int8(hq, wo_q, bo, wo_scale, *, h_scale, bn, bk,
                         activation, out_dtype, out_scale):
    """Quantized output dense with optional (bn, bk) epilogue blocking.

    Unlike the f32 epilogue, *every* split here is exact: int32 partial
    sums associate freely, so ``bk`` K-splits are bitwise-identical to
    the whole-operand dot — the int8 autotuner may bind any block shape
    without a numerics caveat. Dequant (per-channel scale + bias +
    activation) and the optional int8 requant stay in VMEM.
    """
    dcat, dout = wo_q.shape
    bn = dout if bn is None else min(bn, dout)
    bk = dcat if bk is None else min(bk, dcat)
    cols = []
    for j0 in range(0, dout, bn):
        j1 = min(j0 + bn, dout)
        parts = [jax.lax.dot_general(hq[:, k0:min(k0 + bk, dcat)],
                                     wo_q[k0:min(k0 + bk, dcat), j0:j1],
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.int32)
                 for k0 in range(0, dcat, bk)]
        acc = parts[0]
        for p in parts[1:]:
            acc = acc + p
        cols.append(acc)
    acc = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
    scale = h_scale * wo_scale.astype(jnp.float32)      # (1, dout)
    y = acc.astype(jnp.float32) * scale + bo.astype(jnp.float32)
    y = _activate(y, activation)
    if out_dtype == jnp.int8:
        y = jnp.clip(jnp.round(y / out_scale), -127.0, 127.0)
    return y.astype(out_dtype)


def _gravnet_block_int8_cell(xi, xall, maskj, ws_q, bs, wf_q, bf, wo_q, bo,
                             ws_s, wf_s, wo_s, i, *, k, scale, bm, bn, bk,
                             activation, concat_x, x_scale, agg_scale,
                             h_scale, out_scale, out_dtype):
    """One row block of one event, quantized: VMEM requant → int8 S/F
    prologue → f32 aggregate → int8-grid snap → int8 epilogue.

    Mirrors the unfused calibrated chain op for op: S/F dequantize to
    f32 *without* an output snap (the unfused merged projection feeds
    retiles, which keep its output f32), the aggregate output snaps via
    ``agg_scale``, and ``h = concat(original f32 x, snapped agg)``
    requantizes with ``h_scale`` — the concat's calibrated scale.
    """
    q_all = _quant_act(xall, x_scale)
    qi = _quant_act(xi, x_scale)
    s_all = _int8_proj(q_all, ws_q, ws_s, bs, x_scale)
    f_all = _int8_proj(q_all, wf_q, wf_s, bf, x_scale)
    si = _int8_proj(qi, ws_q, ws_s, bs, x_scale)
    agg = _gravnet_cell(si, s_all, f_all, maskj, i, k=k, scale=scale,
                        bm=bm, out_dtype=jnp.float32)
    agg = jnp.clip(jnp.round(agg / agg_scale), -127.0, 127.0) * agg_scale
    h = jnp.concatenate([xi, agg], axis=1) if concat_x else agg
    hq = _quant_act(h, h_scale)
    return _epilogue_dense_int8(hq, wo_q, bo, wo_s, h_scale=h_scale, bn=bn,
                                bk=bk, activation=activation,
                                out_dtype=out_dtype, out_scale=out_scale)


def _gravnet_block_int8_kernel(xi_ref, x_ref, mask_ref, ws_ref, bs_ref,
                               wf_ref, bf_ref, wo_ref, bo_ref, wss_ref,
                               wfs_ref, wos_ref, o_ref, *, k, scale, bm, bn,
                               bk, activation, concat_x, x_scale, agg_scale,
                               h_scale, out_scale, out_dtype):
    o_ref[...] = _gravnet_block_int8_cell(
        xi_ref[...].astype(jnp.float32),       # (bm, dh) query rows
        x_ref[...].astype(jnp.float32),        # (n, dh)  all rows
        mask_ref[...][:, 0],                   # (n,)     validity
        ws_ref[...], bs_ref[...], wf_ref[...], bf_ref[...],
        wo_ref[...], bo_ref[...],
        wss_ref[...], wfs_ref[...], wos_ref[...],
        pl.program_id(0), k=k, scale=scale, bm=bm, bn=bn, bk=bk,
        activation=activation, concat_x=concat_x, x_scale=x_scale,
        agg_scale=agg_scale, h_scale=h_scale, out_scale=out_scale,
        out_dtype=out_dtype)


def _gravnet_block_int8_kernel_batched(xi_ref, x_ref, mask_ref, ws_ref,
                                       bs_ref, wf_ref, bf_ref, wo_ref,
                                       bo_ref, wss_ref, wfs_ref, wos_ref,
                                       o_ref, *, k, scale, bm, bn, bk,
                                       activation, concat_x, x_scale,
                                       agg_scale, h_scale, out_scale,
                                       out_dtype):
    o_ref[0] = _gravnet_block_int8_cell(
        xi_ref[0].astype(jnp.float32),
        x_ref[0].astype(jnp.float32),
        mask_ref[0][:, 0],
        ws_ref[...], bs_ref[...], wf_ref[...], bf_ref[...],
        wo_ref[...], bo_ref[...],
        wss_ref[...], wfs_ref[...], wos_ref[...],
        pl.program_id(1), k=k, scale=scale, bm=bm, bn=bn, bk=bk,
        activation=activation, concat_x=concat_x, x_scale=x_scale,
        agg_scale=agg_scale, h_scale=h_scale, out_scale=out_scale,
        out_dtype=out_dtype)


def gravnet_block_int8_pallas(x, mask, ws_q, bs, wf_q, bf, wo_q, bo,
                              ws_scale, wf_scale, wo_scale, *, x_scale,
                              agg_scale, h_scale, k=8, scale=10.0,
                              activation="relu", concat_x=True, bm=None,
                              bn=None, bk=None, out_dtype=jnp.float32,
                              out_scale=1.0, interpret=False):
    """Quantized GravNet block, one launch. x:(N,dh) f32 → (N, d_out).

    ``ws_q``/``wf_q``/``wo_q`` are int8 per-output-channel quantized
    weights with f32 scale vectors ``*_scale``; ``x_scale``/
    ``agg_scale``/``h_scale`` are the calibrated per-tensor activation
    scales, baked in as compile-time constants. Caller pads N to a
    multiple of ``bm`` (``ops.gravnet_block_int8`` does).
    """
    n, dh = x.shape
    ds, df = ws_q.shape[1], wf_q.shape[1]
    dcat, dout = wo_q.shape
    bm = bm or min(n, 128)
    assert n % bm == 0, (n, bm)
    assert dcat == (dh + 2 * df if concat_x else 2 * df), (dcat, dh, df)
    mask2 = mask.reshape(n, 1).astype(jnp.float32)
    bs2, bf2, bo2 = (bs.reshape(1, ds), bf.reshape(1, df),
                     bo.reshape(1, dout))
    wss2, wfs2, wos2 = (ws_scale.reshape(1, ds), wf_scale.reshape(1, df),
                        wo_scale.reshape(1, dout))
    kern = functools.partial(
        _gravnet_block_int8_kernel, k=k, scale=scale, bm=bm, bn=bn, bk=bk,
        activation=activation, concat_x=concat_x,
        x_scale=float(x_scale), agg_scale=float(agg_scale),
        h_scale=float(h_scale), out_scale=float(out_scale),
        out_dtype=out_dtype)
    return pl.pallas_call(
        kern,
        grid=(n // bm,),
        out_shape=jax.ShapeDtypeStruct((n, dout), out_dtype),
        in_specs=[
            pl.BlockSpec((bm, dh), lambda i: (i, 0)),      # query rows
            pl.BlockSpec((n, dh), lambda i: (0, 0)),       # all rows
            pl.BlockSpec((n, 1), lambda i: (0, 0)),        # mask
            pl.BlockSpec((dh, ds), lambda i: (0, 0)),      # Ws (int8)
            pl.BlockSpec((1, ds), lambda i: (0, 0)),       # bs
            pl.BlockSpec((dh, df), lambda i: (0, 0)),      # Wf (int8)
            pl.BlockSpec((1, df), lambda i: (0, 0)),       # bf
            pl.BlockSpec((dcat, dout), lambda i: (0, 0)),  # Wo (int8)
            pl.BlockSpec((1, dout), lambda i: (0, 0)),     # bo
            pl.BlockSpec((1, ds), lambda i: (0, 0)),       # ws_scale
            pl.BlockSpec((1, df), lambda i: (0, 0)),       # wf_scale
            pl.BlockSpec((1, dout), lambda i: (0, 0)),     # wo_scale
        ],
        out_specs=pl.BlockSpec((bm, dout), lambda i: (i, 0)),
        interpret=interpret,
    )(x, x, mask2, ws_q, bs2, wf_q, bf2, wo_q, bo2, wss2, wfs2, wos2)


def gravnet_block_int8_batched_pallas(x, mask, ws_q, bs, wf_q, bf, wo_q,
                                      bo, ws_scale, wf_scale, wo_scale, *,
                                      x_scale, agg_scale, h_scale, k=8,
                                      scale=10.0, activation="relu",
                                      concat_x=True, bm=None, bn=None,
                                      bk=None, out_dtype=jnp.float32,
                                      out_scale=1.0, interpret=False):
    """Micro-batched quantized GravNet block in ONE kernel launch.

    x:(B,N,dh) f32, mask:(B,N) → (B, N, d_out). Same (B, N/bm) event
    grid as the f32 batched form; weights, per-channel scale vectors,
    and the baked activation scales are shared across the event grid.
    """
    b, n, dh = x.shape
    ds, df = ws_q.shape[1], wf_q.shape[1]
    dcat, dout = wo_q.shape
    bm = bm or min(n, 128)
    assert n % bm == 0, (n, bm)
    assert dcat == (dh + 2 * df if concat_x else 2 * df), (dcat, dh, df)
    mask2 = mask.reshape(b, n, 1).astype(jnp.float32)
    bs2, bf2, bo2 = (bs.reshape(1, ds), bf.reshape(1, df),
                     bo.reshape(1, dout))
    wss2, wfs2, wos2 = (ws_scale.reshape(1, ds), wf_scale.reshape(1, df),
                        wo_scale.reshape(1, dout))
    kern = functools.partial(
        _gravnet_block_int8_kernel_batched, k=k, scale=scale, bm=bm, bn=bn,
        bk=bk, activation=activation, concat_x=concat_x,
        x_scale=float(x_scale), agg_scale=float(agg_scale),
        h_scale=float(h_scale), out_scale=float(out_scale),
        out_dtype=out_dtype)
    return pl.pallas_call(
        kern,
        grid=(b, n // bm),
        out_shape=jax.ShapeDtypeStruct((b, n, dout), out_dtype),
        in_specs=[
            pl.BlockSpec((1, bm, dh), lambda e, i: (e, i, 0)),   # queries
            pl.BlockSpec((1, n, dh), lambda e, i: (e, 0, 0)),    # all rows
            pl.BlockSpec((1, n, 1), lambda e, i: (e, 0, 0)),     # mask
            pl.BlockSpec((dh, ds), lambda e, i: (0, 0)),         # Ws (int8)
            pl.BlockSpec((1, ds), lambda e, i: (0, 0)),          # bs
            pl.BlockSpec((dh, df), lambda e, i: (0, 0)),         # Wf (int8)
            pl.BlockSpec((1, df), lambda e, i: (0, 0)),          # bf
            pl.BlockSpec((dcat, dout), lambda e, i: (0, 0)),     # Wo (int8)
            pl.BlockSpec((1, dout), lambda e, i: (0, 0)),        # bo
            pl.BlockSpec((1, ds), lambda e, i: (0, 0)),          # ws_scale
            pl.BlockSpec((1, df), lambda e, i: (0, 0)),          # wf_scale
            pl.BlockSpec((1, dout), lambda e, i: (0, 0)),        # wo_scale
        ],
        out_specs=pl.BlockSpec((1, bm, dout), lambda e, i: (e, i, 0)),
        interpret=interpret,
    )(x, x, mask2, ws_q, bs2, wf_q, bf2, wo_q, bo2, wss2, wfs2, wos2)
