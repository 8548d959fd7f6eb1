"""GravNet-block megakernel fusion: kernel equivalence, the fusion-pass
rewrite and its lossless-fusion guards, tuning-key plumbing, and the
attention → flash_attention executor route.

The headline invariants (docs/kernels.md):

- a fused f32 ``gravnet_block`` launch is **bitwise-equal** to the
  unfused dense(S)/dense(F) → gravnet_aggregate → concat → dense(out)
  kernel chain at the same shapes; deployed with and without fusion
  (two different compiled programs) the outputs agree within f32
  tolerance for every occupancy bucket, micro-batch width, and k, up
  to the few hits whose k-NN selection flips at a near-tie;
- the quantized ``gravnet_block_int8`` launch matches the calibrated
  unfused int8 chain within **calibration tolerance** (independently
  derived requantization grids may flip boundary values by one step)
  across the same sweep —

both verified end to end through the deployed executor, not just at
the ops layer, using the shared assertions in ``tests/_numerics.py``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _numerics import (assert_bitwise, assert_calibration_close,
                       assert_close, assert_selection_close,
                       assert_ulp_close, backend_sweep,
                       int8_flip_tolerance)

from repro.core import caloclusternet as ccn
from repro.core.graph_ir import Graph, Operator
from repro.core.passes.fusion import fuse
from repro.core.passes.parallelize import Requirements
from repro.core.passes.verify import GraphVerificationError, verify
from repro.core.pipeline import deploy, _cut_hits
from repro.kernels import ops, ref


def _block_operands(seed=0, b=4, n=16, dh=24, ds=3, df=10, dout=24, k=6):
    rng = np.random.default_rng(seed)
    return dict(
        x=jnp.asarray(rng.normal(size=(b, n, dh)), jnp.float32),
        mask=jnp.asarray(rng.uniform(size=(b, n)) < 0.8, jnp.float32),
        ws=jnp.asarray(rng.normal(size=(dh, ds)) * 0.3, jnp.float32),
        bs=jnp.asarray(rng.normal(size=(ds,)), jnp.float32),
        wf=jnp.asarray(rng.normal(size=(dh, df)) * 0.3, jnp.float32),
        bf=jnp.asarray(rng.normal(size=(df,)), jnp.float32),
        wo=jnp.asarray(rng.normal(size=(dh + 2 * df, dout)) * 0.3,
                       jnp.float32),
        bo=jnp.asarray(rng.normal(size=(dout,)), jnp.float32),
    ), k


# ------------------------------------------------------ kernel equivalence ----
def test_gravnet_block_batched_bitwise_matches_per_event():
    o, k = _block_operands()
    batched = ops.gravnet_block_batched(**o, k=k,
                                        backend="pallas_interpret")
    looped = jnp.stack([
        ops.gravnet_block(o["x"][i], o["mask"][i], o["ws"], o["bs"],
                          o["wf"], o["bf"], o["wo"], o["bo"], k=k,
                          backend="pallas_interpret")
        for i in range(o["x"].shape[0])])
    assert_bitwise(batched, looped)   # f32


def test_gravnet_block_matches_unfused_kernel_chain_bitwise():
    """Megakernel output == the three unfused kernel launches it
    replaces, at the exact shapes the executor would run them."""
    o, k = _block_operands()
    b, n, dh = o["x"].shape
    ds, df = o["ws"].shape[1], o["wf"].shape[1]
    fused = ops.gravnet_block_batched(**o, k=k,
                                      backend="pallas_interpret")
    wide = jnp.concatenate([o["ws"], o["wf"]], axis=1)
    bwide = jnp.concatenate([o["bs"], o["bf"]], axis=0)
    sf = ops.fused_dense(o["x"].reshape(b * n, dh), wide, bwide,
                         activation="none", variant="flattened",
                         backend="pallas_interpret"
                         ).reshape(b, n, ds + df)
    agg = ops.gravnet_aggregate_batched(sf[..., :ds], sf[..., ds:],
                                        o["mask"], k=k,
                                        backend="pallas_interpret")
    h = jnp.concatenate([o["x"], agg], axis=-1)
    unfused = ops.fused_dense(h.reshape(b * n, dh + 2 * df), o["wo"],
                              o["bo"], activation="relu",
                              variant="flattened",
                              backend="pallas_interpret"
                              ).reshape(b, n, -1)
    assert_bitwise(fused, unfused)


def test_gravnet_block_xla_path_matches_ref():
    o, k = _block_operands()
    got = ops.gravnet_block_batched(**o, k=k, backend="xla")
    # same jit boundary as the wrapper -> same compiled program, bitwise
    want = jax.jit(lambda **kw: ref.gravnet_block_ref(**kw, k=k))(**o)
    assert_bitwise(got, want)
    # and the eager oracle within float tolerance
    eager = ref.gravnet_block_ref(**o, k=k)
    assert_close(got, eager, rtol=1e-5, atol=1e-6)


def test_gravnet_block_bn_split_bitwise_bk_split_close():
    o, k = _block_operands()
    base = ops.gravnet_block_batched(**o, k=k,
                                     backend="pallas_interpret")
    bn = ops.gravnet_block_batched(**o, k=k, bn=8,
                                   backend="pallas_interpret")
    assert_bitwise(bn, base, context="column split")
    bk = ops.gravnet_block_batched(**o, k=k, bk=16,
                                   backend="pallas_interpret")
    assert_ulp_close(bk, base, max_ulp=16, context="K split")


def test_gravnet_block_row_tiling_matches_unfused_same_bm():
    o, k = _block_operands(n=16)
    fused = ops.gravnet_block_batched(**o, k=k, bm=8,
                                      backend="pallas_interpret")
    b, n, _ = o["x"].shape
    s = ref.fused_dense_ref(o["x"], o["ws"], o["bs"], activation="none",
                            out_dtype=jnp.float32)
    f = ref.fused_dense_ref(o["x"], o["wf"], o["bf"], activation="none",
                            out_dtype=jnp.float32)
    agg = ops.gravnet_aggregate_batched(s, f, o["mask"], k=k, bm=8,
                                        backend="pallas_interpret")
    h = jnp.concatenate([o["x"], agg], axis=-1)
    want = ops.fused_dense(h.reshape(b * n, -1), o["wo"], o["bo"],
                           activation="relu", variant="flattened",
                           backend="pallas_interpret").reshape(b, n, -1)
    assert_bitwise(fused, want)


# ------------------------------------------ int8 kernel equivalence ----
def _int8_block_operands(seed=0, **kw):
    """f32 block operands + per-channel quantized weights + the baked
    activation scales the calibration pass would derive."""
    from repro.core.quantization import quantize_weight
    o, k = _block_operands(seed, **kw)
    q = {}
    for nm in ("ws", "wf", "wo"):
        q[nm + "_q"], q[nm + "_scale"] = quantize_weight(o[nm])
    scales = dict(x_scale=0.02, agg_scale=0.01, h_scale=0.02)
    return o, q, scales, k


def _unfused_int8_chain(o, q, sc, k, backend):
    """The calibrated unfused int8 chain, composed from the per-op
    kernels exactly as the executor runs it: quantize x → int8 S/F
    projections (dequantized, no output snap) → f32 aggregate →
    requantization snap → concat(x, agg) → quantize h → int8 out
    dense."""
    b, n, dh = o["x"].shape
    ds, df = o["ws"].shape[1], o["wf"].shape[1]
    xq = jnp.clip(jnp.round(o["x"] / sc["x_scale"]), -127,
                  127).astype(jnp.int8)
    xs = jnp.asarray([[sc["x_scale"]]], jnp.float32)
    s = ops.fused_dense_int8(xq.reshape(b * n, dh), q["ws_q"], o["bs"],
                             xs, q["ws_scale"], activation="none",
                             backend=backend).reshape(b, n, ds)
    f = ops.fused_dense_int8(xq.reshape(b * n, dh), q["wf_q"], o["bf"],
                             xs, q["wf_scale"], activation="none",
                             backend=backend).reshape(b, n, df)
    agg = ops.gravnet_aggregate_batched(s, f, o["mask"], k=k,
                                        backend=backend)
    agg = jnp.clip(jnp.round(agg / sc["agg_scale"]), -127,
                   127) * sc["agg_scale"]
    h = jnp.concatenate([o["x"], agg], axis=-1)
    hq = jnp.clip(jnp.round(h / sc["h_scale"]), -127,
                  127).astype(jnp.int8)
    hs = jnp.asarray([[sc["h_scale"]]], jnp.float32)
    return ops.fused_dense_int8(hq.reshape(b * n, dh + 2 * df),
                                q["wo_q"], o["bo"], hs, q["wo_scale"],
                                activation="relu",
                                backend=backend).reshape(b, n, -1)


@pytest.mark.parametrize("backend", backend_sweep())
def test_gravnet_block_int8_matches_unfused_int8_chain(backend):
    """The quantized-megakernel headline: one fused launch matches the
    calibrated unfused int8 kernel chain within calibration tolerance
    (requantization boundary values may snap one step apart) on every
    available backend."""
    o, q, sc, k = _int8_block_operands()
    fused = ops.gravnet_block_int8_batched(
        o["x"], o["mask"], q["ws_q"], o["bs"], q["wf_q"], o["bf"],
        q["wo_q"], o["bo"], q["ws_scale"], q["wf_scale"], q["wo_scale"],
        k=k, backend=backend, **sc)
    want = _unfused_int8_chain(o, q, sc, k, backend)
    quantum = int8_flip_tolerance(sc["h_scale"], q["wo_scale"])
    assert_calibration_close(fused, want, quantum=quantum,
                             context=backend)


def test_gravnet_block_int8_batched_bitwise_matches_per_event():
    o, q, sc, k = _int8_block_operands()
    batched = ops.gravnet_block_int8_batched(
        o["x"], o["mask"], q["ws_q"], o["bs"], q["wf_q"], o["bf"],
        q["wo_q"], o["bo"], q["ws_scale"], q["wf_scale"], q["wo_scale"],
        k=k, backend="pallas_interpret", **sc)
    looped = jnp.stack([
        ops.gravnet_block_int8(
            o["x"][i], o["mask"][i], q["ws_q"], o["bs"], q["wf_q"],
            o["bf"], q["wo_q"], o["bo"], q["ws_scale"], q["wf_scale"],
            q["wo_scale"], k=k, backend="pallas_interpret", **sc)
        for i in range(o["x"].shape[0])])
    assert_bitwise(batched, looped)


def test_gravnet_block_int8_matches_ref_oracle():
    o, q, sc, k = _int8_block_operands()
    got = ops.gravnet_block_int8_batched(
        o["x"], o["mask"], q["ws_q"], o["bs"], q["wf_q"], o["bf"],
        q["wo_q"], o["bo"], q["ws_scale"], q["wf_scale"], q["wo_scale"],
        k=k, backend="pallas_interpret", **sc)
    want = ref.gravnet_block_int8_ref(
        o["x"], o["mask"], q["ws_q"], o["bs"], q["wf_q"], o["bf"],
        q["wo_q"], o["bo"], q["ws_scale"], q["wf_scale"], q["wo_scale"],
        k=k, **sc)
    quantum = int8_flip_tolerance(sc["h_scale"], q["wo_scale"])
    assert_calibration_close(got, want, quantum=quantum)


def test_gravnet_block_int8_bn_split_bitwise_bk_split_bitwise():
    """int32 epilogue accumulation makes BOTH splits exact — a numerics
    upgrade over the f32 block, whose bk split only holds to ulps."""
    o, q, sc, k = _int8_block_operands()
    args = (o["x"], o["mask"], q["ws_q"], o["bs"], q["wf_q"], o["bf"],
            q["wo_q"], o["bo"], q["ws_scale"], q["wf_scale"],
            q["wo_scale"])
    base = ops.gravnet_block_int8_batched(*args, k=k,
                                          backend="pallas_interpret",
                                          **sc)
    bn = ops.gravnet_block_int8_batched(*args, k=k, bn=8,
                                        backend="pallas_interpret", **sc)
    assert_bitwise(bn, base, context="column split")
    bk = ops.gravnet_block_int8_batched(*args, k=k, bk=16,
                                        backend="pallas_interpret", **sc)
    assert_bitwise(bk, base, context="K split (exact in int32)")


def test_gravnet_block_int8_requantized_output():
    o, q, sc, k = _int8_block_operands()
    args = (o["x"], o["mask"], q["ws_q"], o["bs"], q["wf_q"], o["bf"],
            q["wo_q"], o["bo"], q["ws_scale"], q["wf_scale"],
            q["wo_scale"])
    out_scale = 0.05
    got = ops.gravnet_block_int8_batched(*args, k=k, out_dtype=jnp.int8,
                                         out_scale=out_scale,
                                         backend="pallas_interpret",
                                         **sc)
    want = ref.gravnet_block_int8_ref(*args, k=k, out_dtype=jnp.int8,
                                      out_scale=out_scale, **sc)
    assert got.dtype == jnp.int8 and want.dtype == jnp.int8
    # flips upstream of the output requant surface as whole int8 steps,
    # so compare dequantized values with the flip bound widened by one
    # output quantum
    quantum = (int8_flip_tolerance(sc["h_scale"], q["wo_scale"])
               + out_scale)
    assert_calibration_close(np.asarray(got, np.float64) * out_scale,
                             np.asarray(want, np.float64) * out_scale,
                             quantum=quantum)


# ----------------------------------------- deployed bitwise acceptance ----
@pytest.mark.parametrize("batch,k", [(1, 4), (1, 8), (8, 4), (8, 8)])
def test_deployed_fused_bitwise_equals_unfused_every_bucket(batch, k):
    """The acceptance sweep: deploy(fuse_gravnet_block=True/False) at
    every occupancy bucket and compare outputs through the Pallas
    (interpret) kernel path: f32 tolerance, up to neighbor-selection
    flips at near-ties (``assert_selection_close``)."""
    cfg = dataclasses.replace(ccn.current_detector_config(), k=k)
    params = ccn.init(jax.random.PRNGKey(1), cfg)
    g = ccn.to_graph(params, cfg)
    rng = np.random.default_rng(7)
    nb = max(batch, 2)
    feeds = {
        "hits": jnp.asarray(rng.normal(size=(nb, cfg.n_hits, cfg.d_in)),
                            jnp.float32),
        "mask": jnp.asarray(rng.uniform(size=(nb, cfg.n_hits)) < 0.7,
                            jnp.float32),
    }
    for bucket in (8, 16, 32):
        req = Requirements(design_point=3, platform="cpu",
                           precision_policy="fp", n_hits=bucket,
                           target_throughput=5e4, max_latency_s=2e-3)
        fb = _cut_hits(feeds, bucket)
        fused = deploy(g, req, kernel_backend="pallas_interpret",
                       batch=batch)(fb)
        unfused = deploy(g, req, kernel_backend="pallas_interpret",
                         batch=batch, fuse_gravnet_block=False)(fb)
        for head in ("beta", "coords", "energy", "cls"):
            assert_selection_close(fused[head], unfused[head],
                                   context=f"bucket={bucket} "
                                           f"head={head}")


def test_deployed_fused_bitwise_on_xla_backend():
    cfg = ccn.current_detector_config()
    params = ccn.init(jax.random.PRNGKey(2), cfg)
    g = ccn.to_graph(params, cfg)
    rng = np.random.default_rng(3)
    feeds = {
        "hits": jnp.asarray(rng.normal(size=(8, cfg.n_hits, cfg.d_in)),
                            jnp.float32),
        "mask": jnp.asarray(rng.uniform(size=(8, cfg.n_hits)) < 0.7,
                            jnp.float32),
    }
    req = Requirements(design_point=3, platform="cpu",
                       precision_policy="fp", n_hits=cfg.n_hits,
                       target_throughput=5e4, max_latency_s=2e-3)
    fused = deploy(g, req, batch=8)(feeds)
    unfused = deploy(g, req, batch=8, fuse_gravnet_block=False)(feeds)
    for head in ("beta", "coords", "energy", "cls"):
        assert_selection_close(fused[head], unfused[head], context=head)


# ------------------------------------- deployed int8 acceptance sweep ----
@pytest.mark.parametrize("backend", backend_sweep())
@pytest.mark.parametrize("batch", [1, 8])
def test_deployed_int8_fused_matches_unfused_every_bucket(batch, backend):
    """The quantized acceptance sweep: under the mixed policy with
    calibration data, ``deploy`` now emits the fused int8 block by
    default; ``fuse_int8=False`` reproduces the legacy unfused
    calibrated chain. The two must agree within calibration tolerance
    (the fused block's scales are re-derived by ``_calibrate_block``
    and may place requantization boundaries one ulp apart) at every
    occupancy bucket, micro-batch width, and backend."""
    g, cfg = _ccn_graph()
    rng = np.random.default_rng(7)
    nb = max(batch, 4)
    feeds = {
        "hits": jnp.asarray(rng.normal(size=(nb, cfg.n_hits, cfg.d_in)),
                            jnp.float32),
        "mask": jnp.asarray(rng.uniform(size=(nb, cfg.n_hits)) < 0.7,
                            jnp.float32),
    }
    for bucket in (8, 16, 32):
        req = Requirements(design_point=3, platform="cpu",
                           precision_policy="mixed", n_hits=bucket,
                           target_throughput=5e4, max_latency_s=2e-3)
        fb = _cut_hits(feeds, bucket)
        fused = deploy(g, req, kernel_backend=backend, batch=batch,
                       calibration_feeds=fb)
        unfused = deploy(g, req, kernel_backend=backend, batch=batch,
                         calibration_feeds=fb, fuse_int8=False)
        blocks = [op for op in fused.graph
                  if op.op_type == "gravnet_block"]
        assert len(blocks) == cfg.n_gravnet_blocks
        for blk in blocks:
            assert blk.precision == "int8"
            assert {"ws_q", "wf_q", "wo_q", "ws_scale", "wf_scale",
                    "wo_scale"} <= set(blk.params)
            for a in ("in_scale", "agg_scale", "h_scale"):
                assert blk.attrs[a] > 0.0
        assert not any(op.op_type == "gravnet_block"
                       for op in unfused.graph)
        # flips=4: a flip inside block 0 can shift block 1's inputs
        # and stack with block 1's own boundary flips
        quantum = max(int8_flip_tolerance(blk.attrs["h_scale"],
                                          blk.params["wo_scale"],
                                          flips=4)
                      for blk in blocks)
        yf, yu = fused(fb), unfused(fb)
        for head in ("beta", "coords", "energy", "cls"):
            assert_calibration_close(
                yf[head], yu[head], quantum=quantum,
                context=f"{backend} bucket={bucket} head={head}")


# --------------------------------------------------- fusion-pass rewrite ----
def _ccn_graph(**over):
    cfg = dataclasses.replace(ccn.current_detector_config(), **over)
    params = ccn.init(jax.random.PRNGKey(0), cfg)
    return ccn.to_graph(params, cfg), cfg


def test_fuse_gravnet_block_rewrites_both_blocks():
    g, cfg = _ccn_graph()
    f = fuse(g, gravnet_block=True)
    blocks = [op for op in f if op.op_type == "gravnet_block"]
    assert len(blocks) == cfg.n_gravnet_blocks
    assert not any(op.op_type == "gravnet_aggregate" for op in f)
    for blk in blocks:
        assert blk.attrs["concat_x"] is True
        assert blk.attrs["activation"] == "relu"
        assert blk.attrs["d_hidden"] == cfg.d_hidden
        assert set(blk.params) == {"ws", "bs", "wf", "bf", "wo", "bo"}
    verify(f)
    # default stays the legacy rewrite, bit-for-bit
    legacy = fuse(g)
    assert [op.name for op in legacy] == [op.name for op in fuse(g)]
    assert not any(op.op_type == "gravnet_block" for op in legacy)


def test_fuse_gravnet_block_preserves_semantics():
    g, cfg = _ccn_graph()
    rng = np.random.default_rng(0)
    feats = jnp.asarray(rng.normal(size=(2, cfg.n_hits, cfg.d_in)),
                        jnp.float32)
    mask = jnp.asarray(rng.uniform(size=(2, cfg.n_hits)) < 0.7,
                       jnp.float32)
    feeds = {"hits": feats, "mask": mask}
    req = Requirements(design_point=2, platform="cpu",
                       precision_policy="fp", n_hits=cfg.n_hits,
                       target_throughput=1e4)
    out = deploy(g, req)(feeds)
    want = ccn.apply(ccn.init(jax.random.PRNGKey(0), cfg), feats, mask,
                     cfg)
    np.testing.assert_allclose(np.asarray(out["beta"][..., 0]),
                               np.asarray(want["beta_logit"]),
                               rtol=1e-4, atol=1e-5)


def test_block_pattern_skips_tapped_aggregate():
    """An extra consumer on the aggregate output (e.g. a monitor tap)
    must keep the chain unfused — the tap needs the materialized
    tensor."""
    g, cfg = _ccn_graph()
    g = g.clone()
    tap = Operator(name="agg_tap", op_type="relu", inputs=["gn0_agg"],
                   out_dim=2 * cfg.d_flr)
    g.insert_after("gn0_agg", tap)
    f = fuse(g, gravnet_block=True)
    names = {op.name for op in f}
    assert "gn0_agg" in names                 # block 0 stayed unfused
    blocks = [op for op in f if op.op_type == "gravnet_block"]
    assert [b.name for b in blocks] == ["gn1_agg.block"]   # block 1 fused


def test_block_pattern_skips_tapped_projection():
    g, cfg = _ccn_graph()
    g = g.clone()
    tap = Operator(name="s_tap", op_type="relu", inputs=["gn0_s"],
                   out_dim=cfg.d_s)
    g.insert_after("gn0_s", tap)
    f = fuse(g, gravnet_block=True)
    assert "gn0_agg" in {op.name for op in f}
    assert [op.name for op in f if op.op_type == "gravnet_block"] \
        == ["gn1_agg.block"]


def test_linear_with_extra_consumer_does_not_fuse_relu():
    """linear → relu only fuses when the relu is the sole consumer."""
    g = Graph()
    g.add(Operator(name="in", op_type="input", out_dim=4,
                   attrs={"feature": "x"}))
    w = jnp.ones((4, 4), jnp.float32)
    g.add(Operator(name="lin", op_type="linear", inputs=["in"],
                   params={"w": w, "b": jnp.zeros((4,))}, out_dim=4))
    g.add(Operator(name="act", op_type="relu", inputs=["lin"], out_dim=4))
    g.add(Operator(name="tap", op_type="relu", inputs=["lin"], out_dim=4))
    g.add(Operator(name="out", op_type="output", inputs=["act", "tap"],
                   attrs={"head_names": ["a", "b"]}, out_dim=8))
    f = fuse(g)
    assert "lin+relu" not in {op.name for op in f}
    assert sum(1 for op in f if op.op_type == "relu") == 2


@pytest.mark.parametrize("mismatch", ["activation", "precision"])
def test_parallel_dense_merge_refuses_mismatch(mismatch):
    g = Graph()
    g.add(Operator(name="in", op_type="input", out_dim=4,
                   attrs={"feature": "x"}))
    w = jnp.ones((4, 3), jnp.float32)
    a = Operator(name="da", op_type="dense", inputs=["in"],
                 params={"w": w, "b": jnp.zeros((3,))}, out_dim=3,
                 attrs={"activation": "relu"})
    b = Operator(name="db", op_type="dense", inputs=["in"],
                 params={"w": w, "b": jnp.zeros((3,))}, out_dim=3,
                 attrs={"activation": "relu"})
    if mismatch == "activation":
        b.attrs["activation"] = "none"
    else:
        b.precision = "int8"
    g.add(a)
    g.add(b)
    g.add(Operator(name="out", op_type="output", inputs=["da", "db"],
                   attrs={"head_names": ["a", "b"]}, out_dim=6))
    f = fuse(g)
    assert {"da", "db"} <= {op.name for op in f}   # no merge happened


def test_verify_rejects_malformed_gravnet_block():
    g, _ = _ccn_graph()
    f = fuse(g, gravnet_block=True)
    bad = f.clone()
    blk = [op for op in bad if op.op_type == "gravnet_block"][0]
    blk.params["wo"] = blk.params["wo"][:-1]   # wrong epilogue K
    with pytest.raises(GraphVerificationError):
        verify(bad)


def _mixed_feeds(cfg, seed=0, nb=4):
    rng = np.random.default_rng(seed)
    return {
        "hits": jnp.asarray(rng.normal(size=(nb, cfg.n_hits, cfg.d_in)),
                            jnp.float32),
        "mask": jnp.asarray(rng.uniform(size=(nb, cfg.n_hits)) < 0.7,
                            jnp.float32),
    }


def _mixed_req(cfg):
    return Requirements(design_point=3, platform="cpu",
                        precision_policy="mixed", n_hits=cfg.n_hits,
                        target_throughput=1e4)


def test_mixed_precision_with_calibration_fuses_int8_block():
    """With calibration data present, the mixed policy's int8 interior
    lowers onto the *quantized* megakernel: the blocks carry quantized
    weights, per-channel scale vectors, and the three baked activation
    scales the kernel requantizes with."""
    g, cfg = _ccn_graph()
    pipe = deploy(g, _mixed_req(cfg),
                  calibration_feeds=_mixed_feeds(cfg))   # default fuse on
    blocks = [op for op in pipe.graph if op.op_type == "gravnet_block"]
    assert len(blocks) == cfg.n_gravnet_blocks
    for blk in blocks:
        assert blk.precision == "int8"
        assert {"ws_q", "wf_q", "wo_q", "ws_scale", "wf_scale",
                "wo_scale"} <= set(blk.params)
        for a in ("in_scale", "agg_scale", "h_scale"):
            assert a in blk.attrs and blk.attrs[a] > 0.0


def test_fuse_int8_escape_hatch_reproduces_legacy_unfused_chain():
    """``fuse_int8=False`` (and ``fuse_gravnet_block=False``) restore
    the legacy mixed deployment: no fused block ops, and the tuning
    problems the graph emits are the legacy unfused families — no
    ``gravnet_block*`` keys."""
    from repro.tuning import graph_kernel_problems
    g, cfg = _ccn_graph()
    feeds = _mixed_feeds(cfg)
    pipe = deploy(g, _mixed_req(cfg), calibration_feeds=feeds,
                  fuse_int8=False)
    assert not any(op.op_type == "gravnet_block" for op in pipe.graph)
    keys = graph_kernel_problems(pipe.graph, n_rows=cfg.n_hits,
                                 backend="xla", batch=4)
    kinds = {k.kernel for k in keys}
    assert "gravnet" in kinds
    assert not any(k.startswith("gravnet_block") for k in kinds)
    # fuse_gravnet_block=False implies the same unfused graph
    pipe2 = deploy(g, _mixed_req(cfg), calibration_feeds=feeds,
                   fuse_gravnet_block=False)
    assert [op.name for op in pipe2.graph] == \
        [op.name for op in pipe.graph]


def test_mixed_without_calibration_is_rejected():
    """The relaxed fusion condition keys off ``calibration_feeds is
    not None`` — sound because ``deploy`` refuses a mixed deployment
    without calibration data outright (an uncalibrated int8 interior
    could otherwise be silently frozen into a fused kernel)."""
    g, cfg = _ccn_graph()
    with pytest.raises(ValueError, match="calibration"):
        deploy(g, _mixed_req(cfg))   # default fuse on, no feeds


# ------------------------------------ int8 fusion guard (direct fuse) ----
def _int8_chain_graph(*, calibrated=True, uniform=True, tap_agg=False,
                      dh=12, ds=3, df=5, dout=12, k=4):
    """A hand-built calibrated int8 block chain for exercising the
    precision-set-aware guard through ``fuse`` directly (the deploy
    flow fuses before the precision policy runs, so only direct fusion
    of an already-calibrated graph reaches these branches)."""
    from repro.core.quantization import quantize_weight
    rng = np.random.default_rng(11)
    g = Graph()
    g.add(Operator(name="x", op_type="input", out_dim=dh,
                   attrs={"feature": "x"}))
    g.add(Operator(name="m", op_type="input", out_dim=1,
                   attrs={"feature": "m"}))

    def _dense(name, inp, d_in, d_out, activation):
        w = jnp.asarray(rng.normal(size=(d_in, d_out)) * 0.3, jnp.float32)
        b = jnp.asarray(rng.normal(size=(d_out,)) * 0.1, jnp.float32)
        op = Operator(name=name, op_type="dense", inputs=[inp],
                      params={"w": w, "b": b}, out_dim=d_out,
                      attrs={"activation": activation},
                      precision="int8")
        if calibrated:
            op.params["w_q"], op.params["w_scale"] = quantize_weight(w)
            op.attrs["in_scale"] = 0.02
        return op

    g.add(_dense("s", "x", dh, ds, "none"))
    g.add(_dense("f", "x", dh, df, "none"))
    agg = Operator(name="agg", op_type="gravnet_aggregate",
                   inputs=["s", "f", "m"],
                   attrs={"k": k, "scale": 10.0, "d_s": ds, "d_f": df},
                   out_dim=2 * df, precision="int8")
    if calibrated:
        agg.attrs["act_scale"] = 0.01
    g.add(agg)
    g.add(Operator(name="cat", op_type="concat", inputs=["x", "agg"],
                   out_dim=dh + 2 * df, precision="int8"))
    g.add(_dense("blk_out", "cat", dh + 2 * df, dout, "relu"))
    if not uniform:
        g["f"].precision = "bf16"
    heads, head_names = ["blk_out"], ["y"]
    if tap_agg:
        g.add(Operator(name="agg_tap", op_type="relu", inputs=["agg"],
                       out_dim=2 * df))
        heads.append("agg_tap")
        head_names.append("tap")
    g.add(Operator(name="out", op_type="output", inputs=heads,
                   attrs={"head_names": head_names},
                   out_dim=dout + (2 * df if tap_agg else 0)))
    g.validate()
    return g


def test_fuse_calibrated_int8_chain_carries_quantization():
    """Direct fusion of an already-calibrated uniform-int8 chain is
    allowed and must carry the quantized weights + scales over, so the
    fused block is executable without re-calibrating."""
    f = fuse(_int8_chain_graph(), gravnet_block=True)
    blocks = [op for op in f if op.op_type == "gravnet_block"]
    assert len(blocks) == 1
    blk = blocks[0]
    assert blk.precision == "int8"
    assert {"ws_q", "wf_q", "wo_q", "ws_scale", "wf_scale",
            "wo_scale"} <= set(blk.params)
    assert blk.attrs["in_scale"] == 0.02
    assert blk.attrs["agg_scale"] == 0.01
    assert blk.attrs["h_scale"] == 0.02    # the out dense's in_scale


def test_fuse_refuses_uncalibrated_int8_chain():
    f = fuse(_int8_chain_graph(calibrated=False), gravnet_block=True)
    assert not any(op.op_type == "gravnet_block" for op in f)
    assert any(op.op_type == "gravnet_aggregate" for op in f)


def test_fuse_refuses_mixed_member_precisions():
    f = fuse(_int8_chain_graph(uniform=False), gravnet_block=True)
    assert not any(op.op_type == "gravnet_block" for op in f)


def test_fuse_refuses_tapped_int8_aggregate():
    f = fuse(_int8_chain_graph(tap_agg=True), gravnet_block=True)
    assert not any(op.op_type == "gravnet_block" for op in f)


# ----------------------------------------------------------- tuning keys ----
def test_gravnet_block_key_batch_dimension():
    from repro.tuning import gravnet_block_key
    from repro.tuning.cache import KernelKey
    k1 = gravnet_block_key(32, 64, 22, 8, "float32", "xla")
    kb = gravnet_block_key(32, 64, 22, 8, "float32", "xla", batch=8)
    assert k1.shape == (32, 64, 22, 8)
    assert kb.shape == (8, 32, 64, 22, 8)      # the 5-dim batched key
    assert KernelKey.decode(kb.encode()) == kb


def test_kernel_opt_binds_cached_block_winner_and_miss_is_default():
    from repro.tuning import TuningCache, gravnet_block_key
    g, cfg = _ccn_graph()
    req = Requirements(design_point=3, platform="cpu",
                       precision_policy="fp", n_hits=cfg.n_hits,
                       target_throughput=5e4, max_latency_s=2e-3)
    # empty cache: no (bm, bn, bk) bindings on the block ops
    pipe0 = deploy(g, req, batch=8, tuning_cache=TuningCache(),
                   kernel_backend="xla")
    for op in pipe0.graph:
        if op.op_type == "gravnet_block":
            assert not any(kn in op.attrs_opt for kn in ("bm", "bn", "bk"))
    cache = TuningCache()
    cache.put(gravnet_block_key(cfg.n_hits, cfg.d_hidden, cfg.d_flr,
                                cfg.k, "float32", "xla", batch=8),
              {"bm": 16, "bn": 32, "d_s": cfg.d_s, "d_out": cfg.d_hidden})
    pipe = deploy(g, req, batch=8, tuning_cache=cache,
                  kernel_backend="xla")
    blocks = [op for op in pipe.graph if op.op_type == "gravnet_block"]
    assert blocks
    for op in blocks:
        assert op.attrs_opt["bm"] == 16 and op.attrs_opt["bn"] == 32
        assert "d_s" not in op.attrs_opt       # replay hints never bind


def test_tune_and_warmup_roundtrip_block_key(tmp_path):
    from repro.tuning import (TuningCache, gravnet_block_key,
                              tune_gravnet_block, warm_from_cache)
    cache = TuningCache(tmp_path / "c.json")
    cfg = tune_gravnet_block(16, 24, 3, 10, 24, 4, batch=3,
                             backend="xla", cache=cache, iters=1)
    assert "bm" in cfg
    key = gravnet_block_key(16, 24, 10, 4, "float32", "xla", batch=3)
    assert key in cache
    entry = cache.entry(key)
    assert entry.config["d_s"] == 3 and entry.config["d_out"] == 24
    assert warm_from_cache(cache, backend="xla") == 1
    # per-event (4-dim) key replays too
    cache.put(gravnet_block_key(16, 24, 10, 4, "float32", "xla"),
              {"bm": 16, "d_s": 3, "d_out": 24})
    assert warm_from_cache(cache, backend="xla") == 2


def test_autotune_graph_searches_block_problems():
    from repro.tuning import TuningCache, autotune_graph
    g, cfg = _ccn_graph()
    req = Requirements(design_point=3, platform="cpu",
                       precision_policy="fp", n_hits=cfg.n_hits,
                       target_throughput=5e4, max_latency_s=2e-3)
    pipe = deploy(g, req, batch=4)
    cache = TuningCache()
    autotune_graph(pipe.graph, n_rows=cfg.n_hits, backend="xla",
                   cache=cache, batch=4, iters=1)
    kinds = {k.kernel for k in cache.entries()}
    assert "gravnet_block" in kinds and "gravnet" not in kinds


# ------------------------------------------------- int8 tuning keys ----
def test_gravnet_block_int8_key_is_distinct_family():
    from repro.tuning import gravnet_block_int8_key, gravnet_block_key
    from repro.tuning.cache import KernelKey
    k8 = gravnet_block_int8_key(32, 64, 22, 8, "xla", batch=8)
    assert k8.kernel == "gravnet_block_int8" and k8.dtype == "int8"
    assert k8.shape == (8, 32, 64, 22, 8)
    assert KernelKey.decode(k8.encode()) == k8
    # never collides with the f32 family even at identical dims
    kf = gravnet_block_key(32, 64, 22, 8, "float32", "xla", batch=8)
    assert k8 != kf and k8.encode() != kf.encode()


def test_kernel_opt_binds_cached_int8_block_winner():
    """A deployed mixed-precision pipeline looks up the dtype-tagged
    int8 key — never the f32 one — and binds only the launch knobs."""
    from repro.tuning import (TuningCache, gravnet_block_int8_key,
                              gravnet_block_key)
    g, cfg = _ccn_graph()
    feeds = _mixed_feeds(cfg)
    cache = TuningCache()
    cache.put(gravnet_block_int8_key(cfg.n_hits, cfg.d_hidden, cfg.d_flr,
                                     cfg.k, "xla", batch=4),
              {"bm": 16, "bn": 32, "d_s": cfg.d_s, "d_out": cfg.d_hidden})
    # an f32 winner at the same dims must NOT leak onto int8 blocks
    cache.put(gravnet_block_key(cfg.n_hits, cfg.d_hidden, cfg.d_flr,
                                cfg.k, "float32", "xla", batch=4),
              {"bm": 8, "bk": 64})
    pipe = deploy(g, _mixed_req(cfg), batch=4, tuning_cache=cache,
                  kernel_backend="xla", calibration_feeds=feeds)
    blocks = [op for op in pipe.graph if op.op_type == "gravnet_block"]
    assert blocks
    for op in blocks:
        assert op.precision == "int8"
        assert op.attrs_opt["bm"] == 16 and op.attrs_opt["bn"] == 32
        assert "bk" not in op.attrs_opt     # the f32 entry did not bind
        assert "d_s" not in op.attrs_opt    # replay hints never bind


def test_tune_and_warmup_roundtrip_int8_block_key(tmp_path):
    from repro.tuning import (TuningCache, gravnet_block_int8_key,
                              tune_gravnet_block, warm_from_cache)
    cache = TuningCache(tmp_path / "c.json")
    cfg = tune_gravnet_block(16, 24, 3, 10, 24, 4, batch=3, dtype="int8",
                             backend="xla", cache=cache, iters=1)
    assert "bm" in cfg
    key = gravnet_block_int8_key(16, 24, 10, 4, "xla", batch=3)
    assert key in cache
    entry = cache.entry(key)
    assert entry.config["d_s"] == 3 and entry.config["d_out"] == 24
    assert warm_from_cache(cache, backend="xla") == 1
    # per-event (4-dim) int8 key replays too
    cache.put(gravnet_block_int8_key(16, 24, 10, 4, "xla"),
              {"bm": 16, "d_s": 3, "d_out": 24})
    assert warm_from_cache(cache, backend="xla") == 2


def test_autotune_graph_searches_int8_block_problems():
    from repro.tuning import TuningCache, autotune_graph
    g, cfg = _ccn_graph()
    pipe = deploy(g, _mixed_req(cfg), batch=4,
                  calibration_feeds=_mixed_feeds(cfg))
    cache = TuningCache()
    autotune_graph(pipe.graph, n_rows=cfg.n_hits, backend="xla",
                   cache=cache, batch=4, iters=1)
    kinds = {k.kernel for k in cache.entries()}
    assert "gravnet_block_int8" in kinds
    assert "gravnet_block" not in kinds and "gravnet" not in kinds


# -------------------------------------------- attention executor route ----
def _attention_graph(n=16, d=8, seed=0):
    rng = np.random.default_rng(seed)
    g = Graph()
    g.add(Operator(name="tok", op_type="input", out_dim=d,
                   attrs={"feature": "tok"}))
    for nm in ("q", "k", "v"):
        w = jnp.asarray(rng.normal(size=(d, d)) * 0.3, jnp.float32)
        g.add(Operator(name=nm, op_type="linear", inputs=["tok"],
                       params={"w": w, "b": jnp.zeros((d,))}, out_dim=d))
    g.add(Operator(name="attn", op_type="attention",
                   inputs=["q", "k", "v"], attrs={"causal": True},
                   out_dim=d))
    g.add(Operator(name="out", op_type="output", inputs=["attn"],
                   attrs={"head_names": ["y"]}, out_dim=d))
    g.validate()
    return g


def test_attention_op_deploys_through_flash_kernel():
    """The flash_attention kernel is reachable from the graph executor:
    ``attention``-typed ops dispatch through it (docs/kernels.md)."""
    g = _attention_graph()
    rng = np.random.default_rng(1)
    tok = jnp.asarray(rng.normal(size=(2, 16, 8)), jnp.float32)
    req = Requirements(design_point=3, platform="cpu",
                       precision_policy="fp", n_hits=16,
                       target_throughput=1e3)
    out = deploy(g, req)({"tok": tok})["y"]
    qkv = [ref.fused_dense_ref(tok, g[nm].params["w"], g[nm].params["b"],
                               activation="none")
           for nm in ("q", "k", "v")]
    want = ref.flash_attention_ref(*qkv, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # interpret backend exercises the Pallas flash kernel body
    out_i = deploy(g, req,
                   kernel_backend="pallas_interpret")({"tok": tok})["y"]
    np.testing.assert_allclose(np.asarray(out_i), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_attention_emits_flash_tuning_key_and_binds_blocks():
    from repro.tuning import (TuningCache, flash_attention_key,
                              graph_kernel_problems)
    g = _attention_graph()
    req = Requirements(design_point=3, platform="cpu",
                       precision_policy="fp", n_hits=16,
                       target_throughput=1e3)
    pipe = deploy(g, req, batch=2)
    keys = graph_kernel_problems(pipe.graph, n_rows=16, backend="xla",
                                 batch=2)
    fk = [k for k in keys if k.kernel == "flash_attention"]
    assert fk and fk[0].shape == (2, 16, 16, 8)
    cache = TuningCache()
    cache.put(flash_attention_key(2, 16, 16, 8, "float32", "xla"),
              {"bq": 16, "bk": 16})
    pipe2 = deploy(g, req, batch=2, tuning_cache=cache,
                   kernel_backend="xla")
    attn = [op for op in pipe2.graph if op.op_type == "attention"][0]
    assert attn.attrs_opt["bq"] == 16 and attn.attrs_opt["bk"] == 16
