"""GatedGCN in its published inference form (benchmarking-gnns): the
deployed graph against the model's eval-mode ``apply``, the
inference-mode ``batchnorm`` op against its formula, the running
statistics against a plain loop, ``knn_edges`` against the benchmark
reference's edge lists, and the training-mode ``apply``. Small sizes on
the CPU: 3 layers of 12 on 32-hit events with 4 in-edges per hit."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph_ir import Graph, Operator, export_graph
from repro.core.passes.parallelize import Requirements
from repro.core.pipeline import deploy
from repro.data.belle2 import current_detector, generate, knn_edges
from repro.models.gnn import gatedgcn
from tests._numerics import assert_close, backend_sweep

N, K = 32, 4
CFG = gatedgcn.GatedGCNConfig(n_layers=3, d_hidden=12, d_in=4,
                              d_edge_in=1, n_classes=3, in_edges=K)


def _graphs(n, seed):
    ev = generate(current_detector(), n, seed)
    return {"nodes": ev["feats"], "node_mask": ev["mask"],
            **knn_edges(ev["feats"], ev["mask"], K)}


def _req():
    return Requirements(design_point=3, platform="cpu",
                        precision_policy="fp", n_hits=N,
                        target_throughput=1e4)


@pytest.fixture(scope="module")
def params():
    return gatedgcn.set_running_stats(
        gatedgcn.init(jax.random.PRNGKey(0), CFG), _graphs(16, 1), CFG)


@pytest.mark.parametrize("backend", backend_sweep())
def test_deploy_matches_eval_apply(params, backend):
    feeds = _graphs(3, 2)
    got = deploy(export_graph("gatedgcn", params, CFG), _req(),
                 kernel_backend=backend)(feeds)["logits"]
    for b in range(3):
        want = gatedgcn.apply(params, {k: v[b] for k, v in feeds.items()},
                              CFG)
        real = feeds["node_mask"][b] > 0
        assert_close(np.asarray(got[b])[real], np.asarray(want)[real],
                     dtype="float32", context=f"{backend}/event{b}")


def test_batchnorm_op_is_the_inference_formula():
    rng = np.random.default_rng(3)
    b, r, d = 2, N, 6
    x = rng.normal(size=(b, r, d)).astype(np.float32) * 3 + 1
    mask = (rng.uniform(size=(b, r)) < 0.7).astype(np.float32)
    bn = {k: rng.uniform(0.5, 2.0, size=d).astype(np.float32)
          for k in ("gamma", "var")}
    bn.update({k: rng.normal(size=d).astype(np.float32)
               for k in ("beta", "mean")})
    g = Graph()
    g.add(Operator(name="x", op_type="input", out_dim=d,
                   attrs={"feature": "x"}))
    g.add(Operator(name="m", op_type="input", out_dim=1,
                   attrs={"feature": "m"}))
    g.add(Operator(name="bn", op_type="batchnorm", inputs=["x", "m"],
                   params={k: jnp.asarray(v) for k, v in bn.items()},
                   attrs={"eps": 1e-5}, out_dim=d))
    g.add(Operator(name="out", op_type="output", inputs=["bn"],
                   attrs={"head_names": ["y"]}, out_dim=d))
    g.validate()
    got = deploy(g, _req(), kernel_backend="xla")({"x": x, "m": mask})["y"]
    want = (bn["gamma"] * (x.astype(np.float64) - bn["mean"])
            / np.sqrt(bn["var"].astype(np.float64) + 1e-5)
            + bn["beta"]) * mask[..., None]
    assert_close(got, want, dtype="float32")
    assert np.all(np.asarray(got)[mask == 0] == 0)


def _plain_stats(p, g):
    """Each layer's BN statistics by a plain float64 loop over events:
    embed, then per layer pool ``A h + gate`` over the real nodes and
    ê over the real edges of every event, normalise with them and
    move on."""
    def lin(q, x):
        return x @ np.asarray(q["w"], np.float64) + np.asarray(q["b"])

    evs = [{k: np.asarray(v[i]) for k, v in g.items()}
           for i in range(len(g["nodes"]))]
    hs = [lin(p["embed_h"], ev["nodes"]) for ev in evs]
    es = [lin(p["embed_e"], ev["edges"]) for ev in evs]
    out = []
    for lp in p["layers"]:
        pres, ehats = [], []
        for ev, h, e in zip(evs, hs, es):
            src, dst = ev["edge_index"]
            ehat = (lin(lp["D"], h)[src] + lin(lp["E"], h)[dst]
                    + lin(lp["C"], e))
            sig = ev["edge_mask"][:, None] / (1 + np.exp(-ehat))
            num, den = np.zeros_like(h), np.zeros_like(h)
            np.add.at(num, dst, sig * lin(lp["B"], h)[src])
            np.add.at(den, dst, sig)
            pres.append(lin(lp["A"], h) + num / (den + 1e-6))
            ehats.append(ehat)
        stats = {}
        for key, xs, mk in (("bn_h", pres, "node_mask"),
                            ("bn_e", ehats, "edge_mask")):
            rows = np.concatenate([x[ev[mk] > 0] for x, ev in zip(xs, evs)])
            stats[key] = (rows.mean(0), rows.var(0))
        out.append(stats)

        def norm(x, key, m):
            mu, var = stats[key]
            return np.maximum((x - mu) / np.sqrt(var + 1e-5), 0) * m[:, None]
        hs = [h + norm(x, "bn_h", ev["node_mask"])
              for h, x, ev in zip(hs, pres, evs)]
        es = [e + norm(x, "bn_e", ev["edge_mask"])
              for e, x, ev in zip(es, ehats, evs)]
    return out


def test_running_stats_match_a_plain_loop(params):
    want = _plain_stats(params, _graphs(16, 1))
    for i, (lp, stats) in enumerate(zip(params["layers"], want)):
        for key in ("bn_h", "bn_e"):
            mu, var = stats[key]
            np.testing.assert_allclose(lp[key]["mean"], mu, rtol=1e-4,
                                       atol=1e-5, err_msg=f"l{i} {key}")
            np.testing.assert_allclose(lp[key]["var"], var, rtol=1e-4,
                                       atol=1e-5, err_msg=f"l{i} {key}")
    # the statistics are the data's, not the initial 0 and 1
    assert not np.allclose(params["layers"][0]["bn_h"]["var"], 1.0)


def _reference():
    import pathlib
    import sys
    root = pathlib.Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from bench import run
    return run.reference({"reference": "gatedgcn"})


@pytest.mark.parametrize("seed", [5, 2**31 + 11])
def test_knn_edges_are_the_reference_pool(seed):
    """The program's ``knn_edges`` and the benchmark reference's ``pool``
    give the same edge lists, slot for slot, on the generator's events
    (whose hits sit on the crystal lattice, where distances tie)."""
    ref = _reference()
    det = {"n_crystals": 576, "grid": [24, 24], "n_hits": N,
           "noise_rate": 8.0, "in_edges": K}
    pool = ref.pool(det, 24, seed)["feeds"]
    got = knn_edges(pool["nodes"], pool["node_mask"], K)
    for key in ("edge_index", "edges", "edge_mask"):
        np.testing.assert_array_equal(got[key], pool[key], err_msg=key)
        assert got[key].dtype == pool[key].dtype


def test_knn_edges_break_ties_by_lower_row():
    """Hit 0 at the centre of a plus of four hits at one distance, a
    fifth farther out: its first three in-edges come from the three
    lowest rows of the plus, and the farther hit is never chosen."""
    feats = np.zeros((1, 8, 4), np.float32)
    xy = [(0.0, 0.0), (0.0, 0.25), (0.25, 0.0), (0.0, -0.25), (-0.25, 0.0),
          (0.5, 0.5)]
    feats[0, :6, 1:3] = xy
    mask = np.zeros((1, 8), np.float32)
    mask[0, :6] = 1.0
    g = knn_edges(feats, mask, 3)
    assert g["edge_index"][0, :, :3].tolist() == [[1, 2, 3], [0, 0, 0]]
    np.testing.assert_array_equal(g["edges"][0, :3, 0], [0.0625] * 3)
    assert g["edge_mask"][0, 3 * 6:].sum() == 0     # padded rows: no edges
    assert g["edge_index"][0, :, 3 * 6:].sum() == 0


def test_train_mode_apply_is_the_batch_statistics_form(params):
    """``train=True`` normalises by the graph's own masked batch
    statistics: with γ and β taken as they are, the eval form with the
    running statistics replaced by those batch statistics."""
    g = {k: jnp.asarray(v[0]) for k, v in _graphs(1, 4).items()}
    got = gatedgcn.apply(params, g, CFG, train=True)

    def batch_form(x, bn, m):
        w = m[:, None]
        n = jnp.maximum(w.sum(), 1.0)
        mu = (x * w).sum(0) / n
        var = (((x - mu) ** 2) * w).sum(0) / n
        return ((x - mu) / jnp.sqrt(var + 1e-5) * bn["gamma"]
                + bn["beta"]) * w

    from repro.nn import dense_apply
    src, dst = g["edge_index"]
    h = dense_apply(params["embed_h"], g["nodes"])
    e = dense_apply(params["embed_e"], g["edges"])
    for lp in params["layers"]:
        ehat = (dense_apply(lp["D"], h[src]) + dense_apply(lp["E"], h[dst])
                + dense_apply(lp["C"], e))
        sig = jax.nn.sigmoid(ehat) * g["edge_mask"][:, None]
        num = jax.ops.segment_sum(sig * dense_apply(lp["B"], h[src]), dst,
                                  num_segments=N)
        den = jax.ops.segment_sum(sig, dst, num_segments=N)
        pre = dense_apply(lp["A"], h) + num / (den + 1e-6)
        h = h + jax.nn.relu(batch_form(pre, lp["bn_h"], g["node_mask"]))
        e = e + jax.nn.relu(batch_form(ehat, lp["bn_e"], g["edge_mask"]))
    for q in params["readout"][:-1]:
        h = jax.nn.relu(dense_apply(q, h))
    want = dense_apply(params["readout"][-1], h)
    real = np.asarray(g["node_mask"]) > 0
    assert_close(np.asarray(got)[real], np.asarray(want)[real],
                 dtype="float32")
    # and it differs from the inference form
    ev = gatedgcn.apply(params, g, CFG)
    assert not np.allclose(np.asarray(ev)[real], np.asarray(got)[real],
                           atol=1e-3)


def test_p_search_costs_the_one_hot_aggregate():
    """The P-search counts an edge aggregate as the kernel runs it, a
    one-hot (n, E) incidence times the (E, d) messages with E =
    in_edges · n, so the published widths on a v5e get a launch the
    model puts within the 2-ms budget, inside the served route's
    16-event service microbatch."""
    from repro.core.op_registry import require_spec
    from repro.launch.mesh import V5E
    pub = gatedgcn.GatedGCNConfig(n_layers=16, d_hidden=70, d_in=4,
                                  d_edge_in=1, n_classes=3, in_edges=8)
    g = export_graph("gatedgcn", gatedgcn.init(jax.random.PRNGKey(0), pub),
                     pub)
    agg = g["l0_agg"]
    flops, act, _ = require_spec(agg).cost(agg, 128, 4.0)
    assert flops == 2.0 * 128 * 1024 * 70 + 2.0 * 128 * 1024
    assert act == (1024 * 70 + 2 * 1024 + 128 * 70) * 4.0
    req = Requirements(design_point=3, platform="tpu", device_kind=V5E,
                       precision_policy="fp", n_hits=128,
                       target_throughput=1e5, max_latency_s=2e-3)
    pipe = deploy(g, req, kernel_backend="pallas")
    par = pipe.graph.meta["parallelization"]
    assert par["model_latency_s"] <= 2e-3
    assert pipe.microbatch <= 16
