"""Tests for the deployment flow: IR, passes, pipeline, quantization, CPS."""
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_support import given, settings, st

from repro.core import caloclusternet as ccn
from repro.core.condensation import condensation_loss
from repro.core.graph_ir import Graph, Operator
from repro.core.passes import fuse, partition
from repro.core.passes.mapping import map_templates
from repro.core.passes.parallelize import Requirements, parallelize
from repro.core.passes.partition import segments
from repro.core.pipeline import deploy
from repro.core.quantization import (apply_precision_policy, fake_quant,
                                     quantize_weight)
from repro.launch.mesh import V5E

CFG = ccn.CCNConfig(n_hits=32)


@pytest.fixture(scope="module")
def setup():
    params = ccn.init(jax.random.PRNGKey(0), CFG)
    rng = np.random.default_rng(0)
    feats = jnp.asarray(rng.normal(size=(4, CFG.n_hits, CFG.d_in)),
                        jnp.float32)
    mask = jnp.asarray(rng.uniform(size=(4, CFG.n_hits)) < 0.7, jnp.float32)
    ref = ccn.apply(params, feats, mask, CFG)
    return params, feats, mask, ref


# ------------------------------------------------------------------ IR ----
def test_graph_topo_validation():
    g = Graph()
    g.add(Operator(name="a", op_type="input", out_dim=4))
    with pytest.raises(ValueError):
        g.add(Operator(name="b", op_type="relu", inputs=["missing"]))
    with pytest.raises(ValueError):
        g.add(Operator(name="a", op_type="relu", inputs=["a"]))


def test_export_graph_structure(setup):
    params, *_ = setup
    g = ccn.to_graph(params, CFG)
    assert len(g.inputs()) == 2 and len(g.outputs()) == 1
    g.validate()
    # parallel dense pairs (gravnet S/FLR, four heads) multicast their input
    assert len(g.multicast_ops()) >= 3


# -------------------------------------------------------------- fusion ----
def test_fusion_removes_multicast_and_relu(setup):
    params, *_ = setup
    g = ccn.to_graph(params, CFG)
    n_relu_before = sum(1 for op in g if op.op_type == "relu")
    assert n_relu_before > 0
    f = fuse(g)
    assert sum(1 for op in f if op.op_type == "relu") == 0
    # head multicast removed: the four heads became one dense + slices
    merged = [op for op in f if op.op_type == "dense"
              and "head_" in op.name and "+" in op.name]
    assert merged and merged[0].out_dim == sum(CFG.head_dims.values())


def test_fusion_is_semantics_preserving(setup):
    params, feats, mask, ref = setup
    g = ccn.to_graph(params, CFG)
    feeds = {"hits": feats, "mask": mask}
    for dp in (1, 2):
        req = Requirements(design_point=dp, platform="cpu",
                           precision_policy="fp", n_hits=CFG.n_hits,
                           target_throughput=1e4)
        out = deploy(g, req)(feeds)
        np.testing.assert_allclose(np.asarray(out["beta"][..., 0]),
                                   np.asarray(ref["beta_logit"]),
                                   rtol=1e-4, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_fusion_property_random_mlp_graph(seed):
    """Fusing a random linear/relu chain graph preserves the output."""
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    dims = [4] + [int(rng.integers(2, 16)) for _ in range(3)]
    g = Graph()
    g.add(Operator(name="hits", op_type="input", out_dim=dims[0],
                   attrs={"feature": "hits"}))
    prev, d_prev = "hits", dims[0]
    for i, d in enumerate(dims[1:]):
        key, k2 = jax.random.split(key)
        w = jax.random.normal(k2, (d_prev, d)) * 0.3
        g.add(Operator(name=f"l{i}", op_type="linear", inputs=[prev],
                       params={"w": w, "b": jnp.zeros((d,))}, out_dim=d))
        if rng.uniform() < 0.7:
            g.add(Operator(name=f"r{i}", op_type="relu", inputs=[f"l{i}"],
                           out_dim=d))
            prev = f"r{i}"
        else:
            prev = f"l{i}"
        d_prev = d
    g.add(Operator(name="out", op_type="output", inputs=[prev],
                   attrs={"head_names": ["y"]}, out_dim=d_prev))
    feeds = {"hits": jnp.asarray(rng.normal(size=(2, 8, dims[0])),
                                 jnp.float32)}
    outs = []
    for dp in (1, 3):
        req = Requirements(design_point=dp, platform="cpu",
                           precision_policy="fp", n_hits=8,
                           target_throughput=1e3)
        outs.append(np.asarray(deploy(g, req)(feeds)["y"]))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-4, atol=1e-5)


# ----------------------------------------------------------- partition ----
def test_partition_targets_and_segments(setup):
    params, *_ = setup
    g = partition(fuse(ccn.to_graph(params, CFG)))
    for op in g:
        if op.op_type in ("gravnet_aggregate", "cps", "input", "output"):
            assert op.target == "xla", op.name
        if op.op_type == "dense":
            assert op.target == "mxu", op.name
    segs = segments(g)
    assert len(segs) == 7  # the paper's seven segments
    targets = [s["target"] for s in segs]
    assert targets == ["xla", "mxu", "xla", "mxu", "xla", "mxu", "xla"]


def test_partition_tpu_native_reduces_segments(setup):
    params, *_ = setup
    g = fuse(ccn.to_graph(params, CFG))
    n_faithful = len(segments(partition(g)))
    n_native = len(segments(partition(g, tpu_native_gravnet=True)))
    assert n_native < n_faithful


# ------------------------------------------------------- parallelization ----
def test_parallelize_meets_reachable_target(setup):
    params, *_ = setup
    g = map_templates(apply_precision_policy(
        partition(fuse(ccn.to_graph(params, CFG))), policy="fp"))
    req = Requirements(target_throughput=1e5, platform="tpu",
                       device_kind=V5E, n_hits=CFG.n_hits)
    gp = parallelize(g, req)
    meta = gp.meta["parallelization"]
    assert meta["model_throughput_ev_s"] >= req.target_throughput
    assert meta["P_mxu"] in {2 ** i for i in range(9)}
    # smallest-P property: halving the chosen P must miss the target
    if meta["P_mxu"] > 1 and meta["P_xla"] > 1:
        req2 = Requirements(target_throughput=1e5, platform="tpu",
                            device_kind=V5E, n_hits=CFG.n_hits,
                            max_p=meta["P_mxu"] // 2)
        gp2 = parallelize(g, req2)
        m2 = gp2.meta["parallelization"]
        assert (m2["model_throughput_ev_s"] < req.target_throughput
                or m2["P_mxu"] + m2["P_xla"] <= meta["P_mxu"] + meta["P_xla"])


# ------------------------------------------------------------- mapping ----
def test_mapping_inserts_retiles(setup):
    params, *_ = setup
    g = map_templates(apply_precision_policy(
        partition(fuse(ccn.to_graph(params, CFG))), policy="fp"))
    retiles = [op for op in g if op.op_type == "retile"]
    assert retiles  # xla<->mxu boundaries need layout changes
    for op in g:
        assert op.template is not None


# --------------------------------------------------------- quantization ----
def test_fake_quant_grid_and_ste():
    x = jnp.linspace(-1.0, 1.0, 101)
    y = fake_quant(x, bits=8)
    assert float(jnp.max(jnp.abs(y - x))) <= 1.0 / 127 + 1e-6
    g = jax.grad(lambda v: jnp.sum(fake_quant(v, bits=8)))(x)
    # STE: unit gradient strictly inside the clip range (0.5 subgradient
    # exactly at the saturation boundary is fine)
    np.testing.assert_allclose(np.asarray(g[1:-1]), 1.0)


def test_quantize_weight_roundtrip():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    wq, ws = quantize_weight(w)
    assert wq.dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(wq, np.float32) * np.asarray(ws),
                               np.asarray(w), atol=float(ws.max()) * 0.51)


def test_mixed_policy_boundary_bf16(setup):
    params, *_ = setup
    g = apply_precision_policy(partition(fuse(ccn.to_graph(params, CFG))),
                               policy="mixed")
    segs = segments(g)
    first, last = segs[0]["id"], segs[-1]["id"]
    for op in g:
        if op.segment in (first, last) or op.op_type in ("input", "output",
                                                         "cps"):
            assert op.precision == "bf16"
        else:
            assert op.precision == "int8"


def test_mixed_precision_pipeline_close_to_fp(setup):
    params, feats, mask, ref = setup
    g = ccn.to_graph(params, CFG)
    feeds = {"hits": feats, "mask": mask}
    req = Requirements(design_point=3, platform="cpu",
                       precision_policy="mixed", n_hits=CFG.n_hits,
                       target_throughput=1e4)
    out = deploy(g, req, calibration_feeds=feeds)(feeds)
    # int8 interior: coarse but bounded deviation (paper: preserved quality)
    err = np.max(np.abs(np.asarray(out["beta"][..., 0])
                        - np.asarray(ref["beta_logit"])))
    assert err < 0.15


# ------------------------------------------------------------------ CPS ----
def test_cps_respects_thresholds(setup):
    params, feats, mask, ref = setup
    res = ccn.cps(ref, mask, CFG)
    beta = jax.nn.sigmoid(ref["beta_logit"]) * mask
    valid = np.asarray(res["cluster_valid"])
    bsel = np.asarray(res["cluster_beta"])
    assert np.all(bsel[valid] > CFG.t_beta)
    # selected points are mutually >= t_dist apart
    xy = np.asarray(res["cluster_xy"])
    for b in range(xy.shape[0]):
        pts = xy[b][valid[b]]
        if len(pts) > 1:
            d = np.linalg.norm(pts[:, None] - pts[None, :], axis=-1)
            d += np.eye(len(pts)) * 1e9
            assert d.min() > CFG.t_dist
    assert np.asarray(res["n_clusters"]).max() <= CFG.k_max


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_cps_property_count_matches_validmask(seed):
    rng = np.random.default_rng(seed)
    outputs = {
        "beta_logit": jnp.asarray(rng.normal(size=(2, 32)), jnp.float32),
        "coords": jnp.asarray(rng.normal(size=(2, 32, 2)), jnp.float32),
        "energy": jnp.asarray(rng.uniform(0, 2, size=(2, 32)), jnp.float32),
    }
    mask = jnp.asarray(rng.uniform(size=(2, 32)) < 0.8, jnp.float32)
    res = ccn.cps(outputs, mask, CFG)
    np.testing.assert_array_equal(
        np.asarray(res["cluster_valid"]).sum(-1),
        np.asarray(res["n_clusters"]))


def _greedy_cps_loop(outputs, mask, cfg):
    """The greedy scan ``ccn.cps`` replaces, kept as its oracle: every hit
    in decreasing β (stable sort), one loop step per hit."""
    def one_event(beta_logit, coords, energy, mask_e):
        beta = jax.nn.sigmoid(beta_logit) * mask_e
        order = jnp.argsort(-beta)

        def body(t, carry):
            sel_xy, sel_e, sel_b, count = carry
            idx = order[t]
            b, c = beta[idx], coords[idx]
            d2 = jnp.sum((sel_xy - c[None, :]) ** 2, axis=1)
            d2 = jnp.where(jnp.arange(cfg.k_max) < count, d2, 1e30)
            ok = ((b > cfg.t_beta) & (jnp.min(d2) > cfg.t_dist ** 2)
                  & (count < cfg.k_max))
            sel_xy = jnp.where(ok, sel_xy.at[count].set(c), sel_xy)
            sel_e = jnp.where(ok, sel_e.at[count].set(energy[idx]), sel_e)
            sel_b = jnp.where(ok, sel_b.at[count].set(b), sel_b)
            return sel_xy, sel_e, sel_b, count + jnp.where(ok, 1, 0)

        init = (jnp.zeros((cfg.k_max, 2), jnp.float32),
                jnp.zeros((cfg.k_max,), jnp.float32),
                jnp.zeros((cfg.k_max,), jnp.float32), jnp.int32(0))
        sel_xy, sel_e, sel_b, count = jax.lax.fori_loop(
            0, beta.shape[0], body, init)
        valid = jnp.arange(cfg.k_max) < count
        return {"cluster_xy": sel_xy, "cluster_e": sel_e,
                "cluster_beta": sel_b, "cluster_valid": valid,
                "n_clusters": count,
                "trigger": jnp.any(valid & (sel_e > cfg.e_trigger))}

    return jax.vmap(one_event)(outputs["beta_logit"], outputs["coords"],
                               outputs["energy"], mask)


_cps_jit = jax.jit(ccn.cps, static_argnums=2)
_greedy_jit = jax.jit(_greedy_cps_loop, static_argnums=2)


def _cps_inputs(kind, batch, n, seed):
    """CPS inputs of one kind; each event keeps 10-100% of its hits."""
    rng = np.random.default_rng(seed)
    logit = rng.normal(0.0, 2.0, (batch, n))
    xy = rng.uniform(-2.0, 2.0, (batch, n, 2))
    mask = rng.uniform(size=(batch, n)) < rng.uniform(0.1, 1.0, (batch, 1))
    if kind == "ties":            # a handful of β values, many equal
        logit = np.round(logit)
    elif kind == "grid":          # 0.01 grid: distances near t_dist
        xy = np.round(rng.uniform(-1.0, 1.0, (batch, n, 2)), 2)
        logit = np.round(logit)
    elif kind == "lattice":       # 0.25 lattice: distances exactly t_dist
        xy = rng.integers(-4, 5, (batch, n, 2)) * 0.25
        logit = np.round(logit)
    elif kind == "all_masked":
        mask[:] = False
    elif kind == "none_above":    # every β under t_beta
        logit = rng.uniform(-6.0, -1.0, (batch, n))
    elif kind == "separable":     # every hit a candidate, all far apart
        logit = rng.uniform(0.0, 3.0, (batch, n))
        mask[:] = True
        side = int(np.ceil(np.sqrt(n)))
        cells = np.stack(np.divmod(np.arange(side * side), side), -1)
        xy = np.stack([cells[rng.permutation(side * side)[:n]]
                       for _ in range(batch)]).astype(np.float64)
    outputs = {"beta_logit": jnp.asarray(logit, jnp.float32),
               "coords": jnp.asarray(xy, jnp.float32),
               "energy": jnp.asarray(rng.uniform(0, 2, (batch, n)),
                                     jnp.float32)}
    return outputs, jnp.asarray(mask, jnp.float32)


@pytest.mark.parametrize("kind", ["random", "ties", "grid", "lattice",
                                  "all_masked", "none_above",
                                  "separable"])
@pytest.mark.parametrize("batch", [1, 16])
@pytest.mark.parametrize("n_hits", [128, 32])
def test_cps_rounds_match_greedy_loop_bitwise(n_hits, batch, kind):
    """``ccn.cps`` (k_max suppression rounds) equals the per-hit greedy
    scan bit for bit, on every output."""
    cfg = ccn.CCNConfig(n_hits=n_hits)
    for seed in range(3):
        outputs, mask = _cps_inputs(kind, batch, n_hits, seed)
        got = _cps_jit(outputs, mask, cfg)
        want = _greedy_jit(outputs, mask, cfg)
        assert got.keys() == want.keys()
        for key in want:
            g, w = np.asarray(got[key]), np.asarray(want[key])
            assert (g.dtype, g.shape) == (w.dtype, w.shape), key
            assert g.tobytes() == w.tobytes(), (key, seed, g, w)
        count = np.asarray(got["n_clusters"])
        if kind in ("all_masked", "none_above"):
            assert not count.any()
        if kind == "separable":
            assert np.all(count == cfg.k_max)


def test_cps_lowers_without_loop_or_sort():
    """The CPS is k_max unrolled rounds: no while loop and no sort at the
    served shape (16 events of 128 hits)."""
    cfg = ccn.CCNConfig()
    f32 = jnp.float32
    outputs = {"beta_logit": jax.ShapeDtypeStruct((16, 128), f32),
               "coords": jax.ShapeDtypeStruct((16, 128, 2), f32),
               "energy": jax.ShapeDtypeStruct((16, 128), f32)}
    lowered = jax.jit(ccn.cps, static_argnums=2).lower(
        outputs, jax.ShapeDtypeStruct((16, 128), f32), cfg)
    assert not re.search(r"stablehlo\.(while|sort)\b", lowered.as_text())
    assert not re.search(r"\b(while|sort)\(", lowered.compile().as_text())


# ------------------------------------------------------------- training ----
def test_condensation_loss_decreases(setup):
    params, feats, mask, _ = setup
    rng = np.random.default_rng(0)
    labels = {
        "object_id": jnp.asarray(rng.integers(-1, 3, size=(4, CFG.n_hits)),
                                 jnp.int32),
        "energy": jnp.asarray(rng.uniform(0, 2, size=(4, CFG.n_hits)),
                              jnp.float32),
        "cls": jnp.asarray(rng.integers(0, 3, size=(4, CFG.n_hits)),
                           jnp.int32),
    }

    def loss_fn(p):
        out = ccn.apply(p, feats, mask, CFG)
        return condensation_loss(out, labels, mask, k_max=CFG.k_max)[0]

    l0, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(l0))
    p2 = jax.tree_util.tree_map(lambda p, g: p - 0.05 * g, params, grads)
    l1 = loss_fn(p2)
    assert float(l1) < float(l0)


# --------------------------------------------------- chip-facing flow ----
def _gatedgcn_graph():
    from repro.core.graph_ir import export_graph
    from repro.models.gnn import gatedgcn
    gcfg = gatedgcn.GatedGCNConfig(n_layers=4, d_hidden=32, d_in=8,
                                   d_edge_in=4, n_classes=2)
    return export_graph("gatedgcn",
                        gatedgcn.init(jax.random.PRNGKey(1), gcfg), gcfg)


def test_segments_with_different_p_rejoin_the_micro_batch():
    """On the chip's peaks the P-search gives MXU and XLA segments
    different P; a segment with P < microbatch runs its events in
    chunks, whose results must rejoin as one (microbatch, ...) batch."""
    from repro.launch.serve import _edge_events
    g = _gatedgcn_graph()
    evs = _edge_events(8, 4)(8, 0)
    feeds = {k: np.stack([e[k] for e in evs]) for k in evs[0]}
    outs = {}
    for plat in ("tpu", "cpu"):
        req = Requirements(design_point=3, platform=plat, n_hits=64,
                           device_kind=V5E if plat == "tpu" else None,
                           precision_policy="fp", target_throughput=1e5,
                           max_latency_s=2e-3)
        pipe = deploy(g, req, kernel_backend="xla")
        par = pipe.par
        outs[plat] = (par["P_mxu"], par["P_xla"], pipe(feeds)["logits"])
    assert outs["tpu"][0] != outs["tpu"][1]      # the chunked case
    assert outs["tpu"][2].shape == (8, 64, 2)
    np.testing.assert_allclose(outs["tpu"][2], outs["cpu"][2],
                               rtol=1e-5, atol=1e-5)


def test_tpu_cost_model_needs_a_known_device_kind(setup):
    params = setup[0]
    g = map_templates(partition(fuse(ccn.to_graph(params, CFG))))
    for kind, says in (("TPU v1 imaginary", "TPU v1 imaginary"),
                       (None, "device_kind")):
        req = Requirements(platform="tpu", device_kind=kind,
                           n_hits=CFG.n_hits)
        with pytest.raises(ValueError, match=says):
            parallelize(g, req)
    # the CPU platform keeps its relative constants and needs no kind
    parallelize(g, Requirements(platform="cpu", n_hits=CFG.n_hits))


def test_deploy_refuses_reference_backend_env_on_tpu(setup, monkeypatch):
    """REPRO_BACKEND is a CPU test lever: on a TPU it must not swap the
    compiled kernels for a reference path in silence."""
    g = ccn.to_graph(setup[0], CFG)
    req = Requirements(platform="tpu", device_kind=V5E,
                       precision_policy="fp", n_hits=CFG.n_hits)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for env in ("xla", "pallas_interpret"):
        monkeypatch.setenv("REPRO_BACKEND", env)
        with pytest.raises(ValueError, match="REPRO_BACKEND"):
            deploy(g, req)
    # an explicit kernel_backend is a choice, not a silent switch
    assert deploy(g, req, kernel_backend="xla").backend == "xla"


def test_calibration_scales_do_not_depend_on_kernel_backend(setup):
    """Calibration records on the jnp reference, so a Pallas and an
    xla deployment bake the same int8 scales and differ only in kernel
    arithmetic."""
    params, feats, mask, _ = setup
    g = ccn.to_graph(params, CFG)
    req = Requirements(platform="cpu", precision_policy="mixed",
                       n_hits=CFG.n_hits, target_throughput=1e4)
    scales = []
    for backend in ("xla", "pallas_interpret"):
        pipe = deploy(g, req, kernel_backend=backend,
                      calibration_feeds={"hits": feats, "mask": mask})
        scales.append({(op.name, k): v for op in pipe.graph
                       for k, v in op.attrs.items() if k.endswith("scale")})
    assert scales[0] and scales[0] == scales[1]
