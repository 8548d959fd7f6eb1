"""Kernel-level microbenchmarks (paper §III-A kernel-level optimization):
fused vs unfused dense, gravnet aggregation vs unfused reference path,
int8 vs fp32 — CPU XLA wall time + derived MXU utilization estimates.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_fn
from repro.kernels import ops, ref
from repro.launch.mesh import V5E, chip_peaks


def run():
    rows = []
    rng = np.random.default_rng(0)
    # trigger-scale fused dense (128 hits x 64->64), batched 4096 events
    m, k, n = 4096 * 128, 64, 64
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(n,)), jnp.float32)

    fused = jax.jit(lambda x_: ops.fused_dense(x_, w, b, backend="xla"))
    t, _ = time_fn(fused, x)
    fl = 2.0 * m * k * n
    rows.append(row("kernel_fused_dense_fp32", t * 1e6,
                    f"{fl / t / 1e9:.1f} GFLOP/s cpu; "
                    f"v5e-roofline "
                    f"{fl / chip_peaks(V5E).flops_bf16 * 1e6:.2f} us"))

    unfused = jax.jit(lambda x_: jnp.maximum(x_ @ w + b, 0.0))
    t2, _ = time_fn(unfused, x)
    rows.append(row("kernel_unfused_linear_relu", t2 * 1e6,
                    f"fused speedup {t2 / t:.2f}x"))

    # int8 path
    xq = jnp.asarray(rng.integers(-127, 127, size=(m, k)), jnp.int8)
    wq = jnp.asarray(rng.integers(-127, 127, size=(k, n)), jnp.int8)
    xs = jnp.asarray([[0.02]], jnp.float32)
    ws = jnp.asarray(rng.uniform(0.001, 0.05, size=(n,)), jnp.float32)
    fq = jax.jit(lambda a: ops.fused_dense_int8(a, wq, b, xs, ws,
                                                backend="xla"))
    t3, _ = time_fn(fq, xq)
    rows.append(row("kernel_fused_dense_int8", t3 * 1e6,
                    f"vs fp32 {t / t3:.2f}x cpu"))

    # gravnet aggregation (upgrade scale: 128 hits, k=8)
    B, N, ds, df = 256, 128, 4, 22
    s = jnp.asarray(rng.normal(size=(B, N, ds)), jnp.float32)
    f = jnp.asarray(rng.normal(size=(B, N, df)), jnp.float32)
    mask = jnp.asarray(rng.uniform(size=(B, N)) < 0.8, jnp.float32)
    gv = jax.jit(jax.vmap(lambda a, b_, m_: ops.gravnet_aggregate(
        a, b_, m_, k=8, backend="xla")))
    t4, _ = time_fn(gv, s, f, mask)
    gfl = 2.0 * B * N * N * (ds + 8 * df)
    rows.append(row("kernel_gravnet_aggregate", t4 / B * 1e6,
                    f"{gfl / t4 / 1e9:.1f} GFLOP/s cpu per-event-us"))
    return rows


if __name__ == "__main__":
    run()
