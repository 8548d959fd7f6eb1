"""Serving warm-up from the tuning cache.

A replica's first request otherwise pays jit tracing + compilation for
every kernel shape it serves — at trigger latency budgets (µs) that is
catastrophic. The tuning cache already knows exactly which
(kernel, shape, dtype, backend) problems the deployment emits, so
``warm_from_cache`` replays each cached winner once with synthetic
operands before the replica accepts traffic, populating the jit cache.

A cache entry that no longer matches the installed kernels (renamed
knob, impossible shape) is skipped and counted: the replay prints how
many it skipped, and the replica starts — a stale cache can make
startup slower, never break it.
"""
from __future__ import annotations

import numpy as np

from repro.tuning.cache import TuningCache


def _replay(key, config) -> None:
    import jax.numpy as jnp

    from repro.kernels import ops
    rng = np.random.default_rng(0)
    backend = key.backend
    if key.kernel == "fused_dense":
        rows, d_in, d_out = key.shape
        if key.dtype == "int8":
            x = jnp.asarray(rng.integers(-127, 127, size=(rows, d_in)),
                            jnp.int8)
            w = jnp.asarray(rng.integers(-127, 127, size=(d_in, d_out)),
                            jnp.int8)
            b = jnp.asarray(rng.normal(size=(d_out,)), jnp.float32)
            xs = jnp.asarray([[0.02]], jnp.float32)
            ws = jnp.asarray(rng.uniform(1e-3, 5e-2, size=(d_out,)),
                             jnp.float32)
            blocks = {k: v for k, v in config.items()
                      if k in ("bm", "bn", "bk")}
            out = ops.fused_dense_int8(x, w, b, xs, ws, backend=backend,
                                       **blocks)
        else:
            dt = jnp.bfloat16 if key.dtype == "bf16" else jnp.float32
            x = jnp.asarray(rng.normal(size=(rows, d_in)), dt)
            w = jnp.asarray(rng.normal(size=(d_in, d_out)), dt)
            b = jnp.asarray(rng.normal(size=(d_out,)), dt)
            out = ops.fused_dense(x, w, b, backend=backend, **config)
    elif key.kernel == "gravnet":
        if len(key.shape) == 5:    # batched problem: (batch, n, ds, df, k)
            batch, n, d_s, d_f, k = key.shape
            s = jnp.asarray(rng.normal(size=(batch, n, d_s)), jnp.float32)
            f = jnp.asarray(rng.normal(size=(batch, n, d_f)), jnp.float32)
            mask = jnp.ones((batch, n), jnp.float32)
            out = ops.gravnet_aggregate_batched(s, f, mask, k=k,
                                                backend=backend, **config)
        else:
            n, d_s, d_f, k = key.shape
            s = jnp.asarray(rng.normal(size=(n, d_s)), jnp.float32)
            f = jnp.asarray(rng.normal(size=(n, d_f)), jnp.float32)
            mask = jnp.ones((n,), jnp.float32)
            out = ops.gravnet_aggregate(s, f, mask, k=k, backend=backend,
                                        **config)
    elif key.kernel == "gravnet_block":
        cfg = dict(config)
        # the 5-dim key carries (batch, n, d_hidden, d_f, k); the
        # remaining block dims ride inside the cached config
        d_s = int(cfg.pop("d_s", 4))
        d_out = int(cfg.pop("d_out", 0))
        activation = cfg.pop("activation", "relu")
        concat_x = bool(cfg.pop("concat_x", True))
        if len(key.shape) == 5:
            batch, n, dh, d_f, k = key.shape
        else:
            n, dh, d_f, k = key.shape
            batch = 1
        d_out = d_out or dh
        dcat = dh + 2 * d_f if concat_x else 2 * d_f
        ws = jnp.asarray(rng.normal(size=(dh, d_s)) * 0.3, jnp.float32)
        bs = jnp.asarray(rng.normal(size=(d_s,)), jnp.float32)
        wf = jnp.asarray(rng.normal(size=(dh, d_f)) * 0.3, jnp.float32)
        bf = jnp.asarray(rng.normal(size=(d_f,)), jnp.float32)
        wo = jnp.asarray(rng.normal(size=(dcat, d_out)) * 0.3, jnp.float32)
        bo = jnp.asarray(rng.normal(size=(d_out,)), jnp.float32)
        if batch > 1:
            x = jnp.asarray(rng.normal(size=(batch, n, dh)), jnp.float32)
            mask = jnp.ones((batch, n), jnp.float32)
            out = ops.gravnet_block_batched(x, mask, ws, bs, wf, bf, wo,
                                            bo, k=k, activation=activation,
                                            concat_x=concat_x,
                                            backend=backend, **cfg)
        else:
            x = jnp.asarray(rng.normal(size=(n, dh)), jnp.float32)
            mask = jnp.ones((n,), jnp.float32)
            out = ops.gravnet_block(x, mask, ws, bs, wf, bf, wo, bo, k=k,
                                    activation=activation,
                                    concat_x=concat_x, backend=backend,
                                    **cfg)
    elif key.kernel == "gravnet_block_int8":
        cfg = dict(config)
        d_s = int(cfg.pop("d_s", 4))
        d_out = int(cfg.pop("d_out", 0))
        activation = cfg.pop("activation", "relu")
        concat_x = bool(cfg.pop("concat_x", True))
        if len(key.shape) == 5:
            batch, n, dh, d_f, k = key.shape
        else:
            n, dh, d_f, k = key.shape
            batch = 1
        d_out = d_out or dh
        dcat = dh + 2 * d_f if concat_x else 2 * d_f
        ws = jnp.asarray(rng.integers(-127, 128, size=(dh, d_s)), jnp.int8)
        wf = jnp.asarray(rng.integers(-127, 128, size=(dh, d_f)), jnp.int8)
        wo = jnp.asarray(rng.integers(-127, 128, size=(dcat, d_out)),
                         jnp.int8)
        bs = jnp.asarray(rng.normal(size=(d_s,)), jnp.float32)
        bf = jnp.asarray(rng.normal(size=(d_f,)), jnp.float32)
        bo = jnp.asarray(rng.normal(size=(d_out,)), jnp.float32)
        wss = jnp.asarray(rng.uniform(1e-3, 5e-2, size=(d_s,)), jnp.float32)
        wfs = jnp.asarray(rng.uniform(1e-3, 5e-2, size=(d_f,)), jnp.float32)
        wos = jnp.asarray(rng.uniform(1e-3, 5e-2, size=(d_out,)),
                          jnp.float32)
        # representative baked scales: warm-up only needs to hit the jit
        # cache for the launch shape/knobs, not the deployment's exact
        # calibration constants (those retrace once, at bind time)
        if batch > 1:
            x = jnp.asarray(rng.normal(size=(batch, n, dh)), jnp.float32)
            mask = jnp.ones((batch, n), jnp.float32)
            out = ops.gravnet_block_int8_batched(
                x, mask, ws, bs, wf, bf, wo, bo, wss, wfs, wos,
                x_scale=0.02, agg_scale=0.01, h_scale=0.02, k=k,
                activation=activation, concat_x=concat_x,
                backend=backend, **cfg)
        else:
            x = jnp.asarray(rng.normal(size=(n, dh)), jnp.float32)
            mask = jnp.ones((n,), jnp.float32)
            out = ops.gravnet_block_int8(
                x, mask, ws, bs, wf, bf, wo, bo, wss, wfs, wos,
                x_scale=0.02, agg_scale=0.01, h_scale=0.02, k=k,
                activation=activation, concat_x=concat_x,
                backend=backend, **cfg)
    elif key.kernel == "edge_aggregate":
        cfg = dict(config)
        reduce = cfg.pop("reduce", "sum")
        if len(key.shape) == 4:   # batched problem: (batch, n, e, d)
            batch, n, e, d = key.shape
            msgs = jnp.asarray(rng.normal(size=(batch, e, d)), jnp.float32)
            ei = jnp.asarray(rng.integers(0, n, size=(batch, 2, e)),
                             jnp.int32)
            mask = jnp.ones((batch, e), jnp.float32)
            out = ops.edge_aggregate_batched(msgs, ei, n, mask,
                                             reduce=reduce,
                                             backend=backend, **cfg)
        else:
            n, e, d = key.shape
            msgs = jnp.asarray(rng.normal(size=(e, d)), jnp.float32)
            ei = jnp.asarray(rng.integers(0, n, size=(2, e)), jnp.int32)
            mask = jnp.ones((e,), jnp.float32)
            out = ops.edge_aggregate(msgs, ei, n, mask, reduce=reduce,
                                     backend=backend, **cfg)
    elif key.kernel == "knn_build":
        if len(key.shape) == 4:   # batched problem: (batch, n, ds, k)
            batch, n, d_s, k = key.shape
            s = jnp.asarray(rng.normal(size=(batch, n, d_s)), jnp.float32)
            seg = jnp.zeros((batch, n), jnp.int32)
            out = ops.knn_build_batched(s, seg, k=k, backend=backend,
                                        **config)
        else:
            n, d_s, k = key.shape
            s = jnp.asarray(rng.normal(size=(n, d_s)), jnp.float32)
            seg = jnp.zeros((n,), jnp.int32)
            out = ops.knn_build(s, seg, k=k, backend=backend, **config)
    elif key.kernel == "knn_aggregate":
        cfg = dict(config)
        scale = float(cfg.pop("scale", 10.0))
        if len(key.shape) == 4:   # batched problem: (batch, n, df, k)
            batch, n, d_f, k = key.shape
            f = jnp.asarray(rng.normal(size=(batch, n, d_f)), jnp.float32)
            idx = jnp.asarray(rng.integers(0, n, size=(batch, n, k)),
                              jnp.int32)
            d2 = jnp.asarray(rng.uniform(0.0, 4.0, size=(batch, n, k)),
                             jnp.float32)
            out = ops.knn_aggregate_batched(f, idx, d2, scale=scale,
                                            backend=backend, **cfg)
        else:
            n, d_f, k = key.shape
            f = jnp.asarray(rng.normal(size=(n, d_f)), jnp.float32)
            idx = jnp.asarray(rng.integers(0, n, size=(n, k)), jnp.int32)
            d2 = jnp.asarray(rng.uniform(0.0, 4.0, size=(n, k)),
                             jnp.float32)
            out = ops.knn_aggregate(f, idx, d2, scale=scale,
                                    backend=backend, **cfg)
    elif key.kernel == "flash_attention":
        bh, s, t, d = key.shape
        q = jnp.asarray(rng.normal(size=(bh, s, d)), jnp.float32)
        kk = jnp.asarray(rng.normal(size=(bh, t, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(bh, t, d)), jnp.float32)
        out = ops.flash_attention(q, kk, v, backend=backend, **config)
    else:
        return
    import jax
    jax.block_until_ready(out)


def warm_from_cache(cache: TuningCache, *, backend: str | None = None,
                    kernels: tuple[str, ...] | None = None) -> int:
    """Replay every cached winner (optionally filtered by backend /
    kernel family) once; returns how many entries were warmed and
    prints how many stale entries it skipped."""
    warmed, skipped = 0, []
    for key, entry in sorted(cache.entries().items(),
                             key=lambda kv: kv[0].encode()):
        if backend is not None and key.backend != backend:
            continue
        if kernels is not None and key.kernel not in kernels:
            continue
        try:
            _replay(key, entry.config)
        except Exception:   # noqa: BLE001 — stale entry must not block start
            skipped.append(key.encode())
            continue
        warmed += 1
    if skipped:
        print(f"[tuning] warm-up skipped {len(skipped)} stale cache "
              f"entr{'y' if len(skipped) == 1 else 'ies'}: "
              f"{', '.join(skipped)}")
    return warmed


def make_warmup(cache: TuningCache, *, backend: str | None = None,
                kernels: tuple[str, ...] | None = None):
    """A no-arg callable for ``ReplicaEngine(warmup_fn=...)``."""
    def _warm():
        return warm_from_cache(cache, backend=backend, kernels=kernels)
    return _warm
