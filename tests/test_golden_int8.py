"""Golden-vector regression test for the quantized GravNet block.

``tests/golden/gravnet_block_int8.npz`` pins one fixed-seed event all
the way through the *unfused calibrated int8 chain*: weights quantized
per-channel with ``quantize_weight``, activation scales derived
calibration-style (absmax of an fp reference run → ``activation_scale``),
and the expected output computed by composing the per-op reference
kernels exactly as the unfused executor does. The fixture freezes
today's numerics so any later change to rounding, scale derivation, or
kernel epilogues shows up as a diff against committed bytes.

Regenerate (after an *intentional* numerics change) with:

    REPRO_REGEN_GOLDEN=1 PYTHONPATH=src python -m pytest \
        tests/test_golden_int8.py -q
"""
import os
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest
from _numerics import (assert_calibration_close, assert_close,
                       assert_fixture_match, backend_sweep,
                       int8_flip_tolerance)

from repro.core.quantization import activation_scale, quantize_weight
from repro.kernels import ops
from repro.kernels import ref as kref

GOLDEN = pathlib.Path(__file__).parent / "golden" / "gravnet_block_int8.npz"

# fixture problem: one event at the mid occupancy bucket
_N, _DH, _DS, _DF, _DOUT, _K, _SEED = 32, 24, 3, 10, 24, 6, 2026


def _generate() -> dict:
    rng = np.random.default_rng(_SEED)
    x = jnp.asarray(rng.normal(size=(_N, _DH)) * 0.4, jnp.float32)
    mask = jnp.asarray(rng.uniform(size=(_N,)) < 0.8, jnp.float32)
    ws = jnp.asarray(rng.normal(size=(_DH, _DS)) * 0.3, jnp.float32)
    bs = jnp.asarray(rng.normal(size=(_DS,)) * 0.1, jnp.float32)
    wf = jnp.asarray(rng.normal(size=(_DH, _DF)) * 0.3, jnp.float32)
    bf = jnp.asarray(rng.normal(size=(_DF,)) * 0.1, jnp.float32)
    wo = jnp.asarray(rng.normal(size=(_DH + 2 * _DF, _DOUT)) * 0.3,
                     jnp.float32)
    bo = jnp.asarray(rng.normal(size=(_DOUT,)) * 0.1, jnp.float32)

    # calibration-style scale derivation from an fp reference run
    x_scale = activation_scale(float(jnp.max(jnp.abs(x))))
    s_fp = kref.fused_dense_ref(x, ws, bs, activation="none",
                                out_dtype=jnp.float32)
    f_fp = kref.fused_dense_ref(x, wf, bf, activation="none",
                                out_dtype=jnp.float32)
    agg_fp = kref.gravnet_aggregate_ref(s_fp, f_fp, mask, k=_K)
    agg_scale = activation_scale(float(jnp.max(jnp.abs(agg_fp))))
    h_fp = jnp.concatenate([x, agg_fp], axis=-1)
    h_scale = activation_scale(float(jnp.max(jnp.abs(h_fp))))

    ws_q, ws_scale = quantize_weight(ws)
    wf_q, wf_scale = quantize_weight(wf)
    wo_q, wo_scale = quantize_weight(wo)

    # expected output: the unfused calibrated chain, per-op references
    xq = jnp.clip(jnp.round(x / x_scale), -127, 127).astype(jnp.int8)
    xs = jnp.asarray([[x_scale]], jnp.float32)
    s = kref.fused_dense_int8_ref(xq, ws_q, bs, xs, ws_scale,
                                  activation="none")
    f = kref.fused_dense_int8_ref(xq, wf_q, bf, xs, wf_scale,
                                  activation="none")
    agg = kref.gravnet_aggregate_ref(s, f, mask, k=_K)
    agg = jnp.clip(jnp.round(agg / agg_scale), -127, 127) * agg_scale
    h = jnp.concatenate([x, agg], axis=-1)
    hq = jnp.clip(jnp.round(h / h_scale), -127, 127).astype(jnp.int8)
    hs = jnp.asarray([[h_scale]], jnp.float32)
    y = kref.fused_dense_int8_ref(hq, wo_q, bo, hs, wo_scale,
                                  activation="relu")

    return dict(x=np.asarray(x), mask=np.asarray(mask),
                ws_q=np.asarray(ws_q), bs=np.asarray(bs),
                wf_q=np.asarray(wf_q), bf=np.asarray(bf),
                wo_q=np.asarray(wo_q), bo=np.asarray(bo),
                ws_scale=np.asarray(ws_scale),
                wf_scale=np.asarray(wf_scale),
                wo_scale=np.asarray(wo_scale),
                x_scale=np.float32(x_scale),
                agg_scale=np.float32(agg_scale),
                h_scale=np.float32(h_scale),
                k=np.int32(_K), y=np.asarray(y))


@pytest.fixture(scope="module")
def golden():
    if os.environ.get("REPRO_REGEN_GOLDEN") == "1":
        GOLDEN.parent.mkdir(parents=True, exist_ok=True)
        np.savez(GOLDEN, **_generate())
    if not GOLDEN.exists():
        pytest.fail(f"missing golden fixture {GOLDEN}; regenerate with "
                    "REPRO_REGEN_GOLDEN=1")
    with np.load(GOLDEN) as z:
        return {k: z[k] for k in z.files}


def _kernel_args(g):
    return ((jnp.asarray(g["x"]), jnp.asarray(g["mask"]),
             jnp.asarray(g["ws_q"]), jnp.asarray(g["bs"]),
             jnp.asarray(g["wf_q"]), jnp.asarray(g["bf"]),
             jnp.asarray(g["wo_q"]), jnp.asarray(g["bo"]),
             jnp.asarray(g["ws_scale"]), jnp.asarray(g["wf_scale"]),
             jnp.asarray(g["wo_scale"])),
            dict(x_scale=float(g["x_scale"]),
                 agg_scale=float(g["agg_scale"]),
                 h_scale=float(g["h_scale"]), k=int(g["k"])))


def test_golden_fixture_is_current(golden):
    """Regenerating from source reproduces the fixture — the int8
    weights exactly, scales and outputs within f32 tolerance: the
    fixture and the calibration/quantization code have not drifted."""
    assert_fixture_match(_generate(), golden)


def test_ref_oracle_matches_golden(golden):
    """The fused-block oracle reproduces the unfused-chain golden
    output near-exactly (same grids, same int32 accumulation)."""
    args, sc = _kernel_args(golden)
    y = kref.gravnet_block_int8_ref(*args, **sc)
    assert_close(y, golden["y"], dtype="int8")


@pytest.mark.parametrize("backend", backend_sweep())
def test_fused_kernel_matches_golden(backend, golden):
    """The fused megakernel reproduces the golden unfused-chain output
    within calibration tolerance on every available backend."""
    args, sc = _kernel_args(golden)
    y = ops.gravnet_block_int8(*args, backend=backend, **sc)
    quantum = int8_flip_tolerance(float(golden["h_scale"]),
                                  golden["wo_scale"])
    assert_calibration_close(y, golden["y"], quantum=quantum,
                             context=backend)
