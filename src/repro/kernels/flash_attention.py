"""Pallas TPU kernel: blockwise (flash) causal attention.

Beyond-paper kernel for the LM prefill cells (§Roofline shows prefill is
memory-bound at baseline: the jnp path materializes (Bq, T) score tiles
through HBM). Standard streaming-softmax schedule:

  grid = (B·H, S/bq, T/bk)   (kv innermost — TPU 'arbitrary' dim, so the
                              VMEM scratch carries across kv steps)
  per (q-block, kv-block):
    s   = q·kᵀ / sqrt(d)  (+ causal mask)
    m'  = max(m, rowmax(s));  p = exp(s − m')
    l   = l·exp(m − m') + rowsum(p)
    acc = acc·exp(m − m') + p·v
  epilogue (last kv block): o = acc / l

Causal skipping of fully-masked kv blocks is done with `pl.when`
(zero-work guard); the q/kv block shapes are MXU-aligned (128 lanes).
Validated in interpret mode against ref softmax attention (tests).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal, nk, bq, bk, scale, out_dtype):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step():
        q = q_ref[0].astype(jnp.float32)             # (bq, d)
        k = k_ref[0].astype(jnp.float32)             # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=mxu.precision(q, k),
                                preferred_element_type=jnp.float32)
        s = s * scale                                 # (bq, bk)
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            cols = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(cols <= rows, s, -1e30)
        m_prev = m_ref[...]                           # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                        # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)               # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1,
                                                  keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=mxu.precision(p, v),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # kv blocks strictly after the q block contribute nothing
        pl.when(ki * bk <= qi * bq + bq - 1)(_step)
    else:
        _step()

    @pl.when(ki == nk - 1)
    def _epilogue():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(out_dtype)


def flash_attention_pallas(q, k, v, *, causal=True, bq=128, bk=128,
                           interpret=False):
    """q: (BH, S, D), k/v: (BH, T, D) -> (BH, S, D).

    S % bq == 0 and T % bk == 0 (ops wrapper pads); same-head layout
    (GQA callers repeat/reshape kv beforehand)."""
    bh, s, d = q.shape
    t = k.shape[1]
    bq = min(bq, s)
    bk = min(bk, t)
    assert s % bq == 0 and t % bk == 0
    nq, nk = s // bq, t // bk
    scale = 1.0 / math.sqrt(d)
    kern = functools.partial(_flash_kernel, causal=causal, nk=nk, bq=bq,
                             bk=bk, scale=scale, out_dtype=q.dtype)
    return pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom
            pltpu.VMEM((bq, d), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
