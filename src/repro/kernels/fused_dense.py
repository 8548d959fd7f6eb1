"""Pallas TPU kernel: fused Dense = matmul + bias + activation (+ int8 path).

This is the direct analogue of the paper's AIE ``Dense`` operator (the
result of the operator-fusion pass: Linear + ReLU fused, parallel Linears
merged into one wide matmul). Two variants mirror the paper's kernel-level
optimization study:

- ``looped``    — grid-tiled (M/bm, N/bn, K/bk) matmul with an f32 VMEM
                  accumulator; the general high-throughput form (the AIE
                  "loop-pipelined" kernel).
- ``flattened`` — single-grid-cell kernel with the whole operand set
                  resident in VMEM and no K loop; for the tiny
                  trigger-scale matrices (≤ a few hundred rows) where
                  per-iteration scheduling overhead dominates — the
                  ``chess_flatten_loop`` analogue (trades program/VMEM
                  footprint for issue efficiency).

The int8 kernel implements the paper's 8-bit interior precision: int8 ×
int8 → int32 MXU accumulation, per-channel weight scales + per-tensor
activation scale dequant in the epilogue, optional requantization to int8
for kernel-to-kernel handoff inside a partition.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu


def _activate(y, activation: str | None):
    if activation in (None, "none", "linear"):
        return y
    if activation == "relu":
        return jnp.maximum(y, 0.0)
    if activation == "gelu":
        return jax.nn.gelu(y)
    if activation == "silu":
        return jax.nn.silu(y)
    raise ValueError(f"unknown activation {activation!r}")


# ------------------------------------------------------------- fp kernels ----
def _looped_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, activation, nk,
                   out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += mxu.dot(x_ref[...], w_ref[...])

    @pl.when(k == nk - 1)
    def _epilogue():
        y = acc_ref[...]
        if b_ref is not None:
            y = y + b_ref[...].astype(jnp.float32)
        o_ref[...] = _activate(y, activation).astype(out_dtype)


def _flattened_kernel(x_ref, w_ref, b_ref, o_ref, *, activation, out_dtype):
    y = mxu.dot(x_ref[...], w_ref[...])
    if b_ref is not None:
        y = y + b_ref[...].astype(jnp.float32)
    o_ref[...] = _activate(y, activation).astype(out_dtype)


def _flattened_kernel_batched(x_ref, w_ref, b_ref, o_ref, *, activation,
                              out_dtype):
    # leading block dim 1 = one event per grid cell; weights/bias are
    # shared across the event grid (their BlockSpecs ignore the index)
    y = mxu.dot(x_ref[0], w_ref[...])
    if b_ref is not None:
        y = y + b_ref[...].astype(jnp.float32)
    o_ref[0] = _activate(y, activation).astype(out_dtype)


def fused_dense_pallas(x, w, b=None, *, activation="relu", variant="looped",
                       bm=128, bn=128, bk=512, out_dtype=None,
                       interpret=False):
    """y = act(x @ w + b). x:(M,K) w:(K,N) b:(N,)|None.

    Dims must tile evenly (``ops.fused_dense`` pads); out_dtype defaults to
    x.dtype.
    """
    m, kdim = x.shape
    _, n = w.shape
    out_dtype = out_dtype or x.dtype
    b2 = None if b is None else b.reshape(1, n)
    has_b = b2 is not None

    if variant == "flattened":
        if has_b:
            kern = functools.partial(_flattened_kernel, activation=activation,
                                     out_dtype=out_dtype)
        else:
            kern = lambda x_ref, w_ref, o_ref: _flattened_kernel(  # noqa: E731
                x_ref, w_ref, None, o_ref, activation=activation,
                out_dtype=out_dtype)
        in_specs = [pl.BlockSpec((m, kdim), lambda: (0, 0)),
                    pl.BlockSpec((kdim, n), lambda: (0, 0))]
        if has_b:
            in_specs.append(pl.BlockSpec((1, n), lambda: (0, 0)))
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((m, n), lambda: (0, 0)),
            interpret=interpret,
        )(*((x, w, b2) if has_b else (x, w)))

    assert variant == "looped", variant
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, kdim)
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, \
        (m, n, kdim, bm, bn, bk)
    nk = kdim // bk
    if has_b:
        kern = functools.partial(_looped_kernel, activation=activation, nk=nk,
                                 out_dtype=out_dtype)
    else:
        kern = lambda x_ref, w_ref, o_ref, acc_ref: _looped_kernel(  # noqa: E731
            x_ref, w_ref, None, o_ref, acc_ref, activation=activation, nk=nk,
            out_dtype=out_dtype)
    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))]
    if has_b:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn, nk),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(*((x, w, b2) if has_b else (x, w)))


def fused_dense_batched_pallas(x, w, b=None, *, activation="relu",
                               variant="flattened", bm=128, bn=128, bk=512,
                               out_dtype=None, interpret=False):
    """Micro-batched fused dense in ONE kernel launch.

    x:(B,M,K) w:(K,N) b:(N,)|None -> (B,M,N). Two batch-packing forms,
    mirroring the per-event variants:

    - ``flattened`` — grid (B,): the leading grid dimension walks one
      event per cell with the whole per-event operand set VMEM-resident
      (weights shared across cells). Keeps the tiny-matrix issue
      efficiency of the flattened kernel while amortizing the launch
      over the micro-batch.
    - ``looped``    — events are *row-packed*: (B,M,K) reshapes to
      (B·M, K) and reuses the grid-tiled looped kernel, so the MXU sees
      one tall matmul (dense ops have no cross-row coupling, so packing
      is exact). The caller's (bm, bn, bk) tile the packed shape.
    """
    bsz, m, kdim = x.shape
    _, n = w.shape
    out_dtype = out_dtype or x.dtype
    if variant == "looped":
        y = fused_dense_pallas(x.reshape(bsz * m, kdim), w, b,
                               activation=activation, variant="looped",
                               bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
                               interpret=interpret)
        return y.reshape(bsz, m, n)
    assert variant == "flattened", variant
    b2 = None if b is None else b.reshape(1, n)
    has_b = b2 is not None
    if has_b:
        kern = functools.partial(_flattened_kernel_batched,
                                 activation=activation, out_dtype=out_dtype)
    else:
        kern = lambda x_ref, w_ref, o_ref: _flattened_kernel_batched(  # noqa: E731
            x_ref, w_ref, None, o_ref, activation=activation,
            out_dtype=out_dtype)
    in_specs = [pl.BlockSpec((1, m, kdim), lambda e: (e, 0, 0)),
                pl.BlockSpec((kdim, n), lambda e: (0, 0))]
    if has_b:
        in_specs.append(pl.BlockSpec((1, n), lambda e: (0, 0)))
    return pl.pallas_call(
        kern,
        grid=(bsz,),
        out_shape=jax.ShapeDtypeStruct((bsz, m, n), out_dtype),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, m, n), lambda e: (e, 0, 0)),
        interpret=interpret,
    )(*((x, w, b2) if has_b else (x, w)))


# ----------------------------------------------------------- int8 kernels ----
def _looped_kernel_q(x_ref, w_ref, b_ref, xs_ref, ws_ref, o_ref, acc_ref, *,
                     activation, nk, out_dtype, out_scale):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        scale = xs_ref[0, 0] * ws_ref[...].astype(jnp.float32)   # (1, bn)
        y = acc_ref[...].astype(jnp.float32) * scale
        if b_ref is not None:
            y = y + b_ref[...].astype(jnp.float32)
        y = _activate(y, activation)
        if out_dtype == jnp.int8:
            y = jnp.clip(jnp.round(y / out_scale), -127.0, 127.0)
        o_ref[...] = y.astype(out_dtype)


def fused_dense_int8_pallas(x_q, w_q, b, x_scale, w_scale, *,
                            activation="relu", bm=128, bn=128, bk=512,
                            out_dtype=jnp.float32, out_scale=1.0,
                            interpret=False):
    """Quantized fused dense.

    x_q:(M,K) int8, w_q:(K,N) int8, x_scale:(1,1) f32 per-tensor,
    w_scale:(N,) f32 per-channel, b:(N,) f32 (dequantized domain) or None.
    ``out_dtype=int8`` requantizes with ``out_scale`` for in-partition
    kernel-to-kernel handoff; f32/bf16 dequantizes at partition boundaries
    (the paper's 16-bit boundary precision).
    """
    m, kdim = x_q.shape
    _, n = w_q.shape
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, kdim)
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0
    nk = kdim // bk
    b2 = None if b is None else b.reshape(1, n)
    has_b = b2 is not None
    ws2 = w_scale.reshape(1, n)
    if has_b:
        kern = functools.partial(_looped_kernel_q, activation=activation,
                                 nk=nk, out_dtype=out_dtype,
                                 out_scale=out_scale)
    else:
        kern = lambda x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref: (  # noqa: E731
            _looped_kernel_q(x_ref, w_ref, None, xs_ref, ws_ref, o_ref,
                             acc_ref, activation=activation, nk=nk,
                             out_dtype=out_dtype, out_scale=out_scale))
    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))]
    args = [x_q, w_q]
    if has_b:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        args.append(b2)
    in_specs += [pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
                 pl.BlockSpec((1, bn), lambda i, j, k: (0, j))]
    args += [x_scale, ws2]
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn, nk),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(*args)
