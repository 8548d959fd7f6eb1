"""End-to-end deploy of the model-zoo GNNs through the pattern-keyed
flow: exporter registry, edge-typed IR lowering, and deployed-vs-eager
numerics on every backend.

The acceptance claim of the model-agnostic flow: a model joins deploy()
by registering a ``to_graph`` exporter, and the compiled pipeline
reproduces the eager ``apply`` within the shared dtype tolerances —
with no model-specific branches in any pass.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import caloclusternet as ccn
from repro.core.graph_ir import export_graph, exporters
from repro.core.op_registry import UnknownOperatorError
from repro.core.pipeline import deploy
from repro.core.passes.parallelize import Requirements
from repro.models.gnn import gatedgcn, graphsage
from tests._numerics import assert_close, backend_sweep

jax.config.update("jax_platform_name", "cpu")

N, E, B = 32, 128, 3     # E = 4N, the registry's default edge budget

GGCN_CFG = gatedgcn.GatedGCNConfig(n_layers=2, d_hidden=16, d_in=8,
                                   d_edge_in=4, n_classes=4)
SAGE_CFG = graphsage.GraphSAGEConfig(n_layers=2, d_hidden=16, d_in=12,
                                     n_classes=5, normalize=True)


def _req():
    return Requirements(design_point=3, platform="cpu",
                        precision_policy="fp", n_hits=N,
                        target_throughput=1e4)


def _edge_feeds(d_in, d_edge_in=None, *, seed=0):
    rng = np.random.default_rng(seed)
    feeds = {
        "nodes": jnp.asarray(rng.normal(size=(B, N, d_in)), jnp.float32),
        "edge_index": jnp.asarray(rng.integers(0, N, size=(B, 2, E)),
                                  jnp.int32),
        "node_mask": jnp.asarray(rng.uniform(size=(B, N)) < 0.8,
                                 jnp.float32),
        "edge_mask": jnp.asarray(rng.uniform(size=(B, E)) < 0.7,
                                 jnp.float32),
    }
    if d_edge_in is not None:
        feeds["edges"] = jnp.asarray(rng.normal(size=(B, E, d_edge_in)),
                                     jnp.float32)
    return feeds


def _event(feeds, b):
    return {k: v[b] for k, v in feeds.items()}


def _ggcn_params(seed):
    """GatedGCN weights with its batch-norms' running statistics set on
    calibration graphs, so that the served inference-mode BN is not
    near the identity."""
    calib = _edge_feeds(GGCN_CFG.d_in, GGCN_CFG.d_edge_in, seed=100 + seed)
    return gatedgcn.set_running_stats(
        gatedgcn.init(jax.random.PRNGKey(seed), GGCN_CFG), calib, GGCN_CFG)


# ------------------------------------------------------------- registry ----
def test_exporter_registry_lists_models():
    names = exporters()
    for name in ("caloclusternet", "gatedgcn", "graphsage"):
        assert name in names, names


def test_export_graph_unknown_model():
    with pytest.raises(KeyError, match="no exporter 'resnet'"):
        export_graph("resnet", {}, None)


def test_export_graph_matches_direct_to_graph():
    params = ccn.init(jax.random.PRNGKey(0), ccn.CCNConfig())
    via_registry = export_graph("caloclusternet", params, ccn.CCNConfig())
    direct = ccn.to_graph(params, ccn.CCNConfig())
    assert ([(o.name, o.op_type, o.inputs) for o in via_registry]
            == [(o.name, o.op_type, o.inputs) for o in direct])


def test_export_preflight_rejects_unregistered_ops():
    from repro.core.graph_ir import Graph, Operator, register_exporter

    def bad_export(params, cfg):
        g = Graph()
        g.add(Operator(name="x", op_type="input", out_dim=4,
                       attrs={"feature": "x"}))
        g.add(Operator(name="mystery", op_type="septic_pool",
                       inputs=["x"], out_dim=4))
        g.add(Operator(name="out", op_type="output", inputs=["mystery"],
                       attrs={"head_names": ["y"]}, out_dim=4))
        g.validate()
        return g

    register_exporter("_test_bad_model", bad_export)
    with pytest.raises(UnknownOperatorError,
                       match=r"mystery \('septic_pool'\)"):
        export_graph("_test_bad_model", {}, None)


def test_gatedgcn_export_rejects_graph_readout():
    cfg = gatedgcn.GatedGCNConfig(n_layers=1, d_hidden=8, d_in=4,
                                  readout="graph")
    params = gatedgcn.init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="readout='node'"):
        gatedgcn.to_graph(params, cfg)


# ------------------------------------------------- deployed vs eager ----
@pytest.mark.parametrize("backend", backend_sweep())
def test_gatedgcn_deploy_matches_eager(backend):
    """The served graph's inference-mode batch-norms agree with the
    eager ``apply`` (running statistics, the default mode)."""
    params = _ggcn_params(1)
    g = export_graph("gatedgcn", params, GGCN_CFG)
    pipe = deploy(g, _req(), kernel_backend=backend)
    feeds = _edge_feeds(GGCN_CFG.d_in, GGCN_CFG.d_edge_in)
    got = pipe(feeds)["logits"]
    assert got.shape == (B, N, GGCN_CFG.n_classes)
    for b in range(B):
        want = gatedgcn.apply(params, _event(feeds, b), GGCN_CFG)
        assert_close(got[b], want, dtype="float32",
                     context=f"{backend}/event{b}")


@pytest.mark.parametrize("backend", backend_sweep())
def test_graphsage_deploy_matches_eager(backend):
    params = graphsage.init(jax.random.PRNGKey(2), SAGE_CFG)
    g = export_graph("graphsage", params, SAGE_CFG)
    pipe = deploy(g, _req(), kernel_backend=backend)
    feeds = _edge_feeds(SAGE_CFG.d_in, seed=4)
    got = pipe(feeds)["logits"]
    assert got.shape == (B, N, SAGE_CFG.n_classes)
    for b in range(B):
        want = graphsage.apply(params, _event(feeds, b), SAGE_CFG)
        assert_close(got[b], want, dtype="float32",
                     context=f"{backend}/event{b}")


def test_gatedgcn_deploy_batched_executable():
    """The batch-packed executable (one whole-batch launch per segment)
    agrees with the per-event-shaped one."""
    params = _ggcn_params(1)
    g = export_graph("gatedgcn", params, GGCN_CFG)
    feeds = _edge_feeds(GGCN_CFG.d_in, GGCN_CFG.d_edge_in, seed=9)
    lo = deploy(export_graph("gatedgcn", params, GGCN_CFG), _req(),
                kernel_backend="xla")(feeds)["logits"]
    hi = deploy(g, _req(), kernel_backend="xla", batch=B)(feeds)["logits"]
    assert_close(hi, lo, dtype="float32", context="batched-vs-looped")


def test_gatedgcn_deploy_all_design_points():
    """Every design point lowers the edge-typed ops (partition, fuse,
    parallelize, kernel_opt all see them) and agrees with the eager
    inference-mode ``apply``."""
    params = _ggcn_params(3)
    feeds = _edge_feeds(GGCN_CFG.d_in, GGCN_CFG.d_edge_in, seed=6)
    want = gatedgcn.apply(params, _event(feeds, 0), GGCN_CFG)
    for dp in (1, 2, 3):
        req = Requirements(design_point=dp, platform="cpu",
                           precision_policy="fp", n_hits=N,
                           target_throughput=1e4)
        g = export_graph("gatedgcn", params, GGCN_CFG)
        got = deploy(g, req, kernel_backend="xla")(feeds)["logits"]
        assert_close(got[0], want, dtype="float32", context=f"dp{dp}")
