"""GatedGCN (Bresson & Laurent, arXiv:1711.07553) in the benchmarking-gnns
form (Dwivedi et al., arXiv:2003.00982; node classification: 16 layers,
d_hidden=70, residual connections, batch-norm, edge gates, MLPReadout
with L=2).

Layer, with i the destination and j the source of an edge; padded edges
add nothing, because the gate is multiplied by the edge mask:

    ê_ij = D h_j + E h_i + C e_ij          σ_ij = sigmoid(ê_ij) · mask_ij
    h_i' = h_i + ReLU(BN_h(A h_i + Σ_j σ_ij ⊙ B h_j / (Σ_j σ_ij + 1e-6)))
    e_ij' = e_ij + ReLU(BN_e(ê_ij))

Every matrix has a bias. ``h⁰ = embed_h(nodes)``, ``e⁰ = embed_e(edges)``;
the readout is the MLP ``d → d/2 → … → d/2^L → n_classes`` with ReLU
between (L = ``readout_layers``). At inference (``apply``'s default)
each BN is PyTorch's ``eval()`` form, ``γ (x − μ_run) / sqrt(σ²_run +
1e-5) + β`` from its running statistics, which ``set_running_stats``
sets from calibration graphs; ``apply(..., train=True)`` normalises by
the masked batch statistics instead (``loss_fn``). Dropout is off.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.graph_ir import Graph, Operator, register_exporter
from repro.dist.sharding import DP, TP
from repro.models.gnn import common as C
from repro.nn import dense_apply, dense_init

BN_EPS = 1e-5
GATE_EPS = 1e-6
_LAYER_MATS = ("A", "B", "C", "D", "E")


@dataclasses.dataclass(frozen=True)
class GatedGCNConfig:
    name: str = "gatedgcn"
    n_layers: int = 16
    d_hidden: int = 70
    d_in: int = 1433
    d_edge_in: int = 1
    n_classes: int = 7
    readout_layers: int = 2    # MLPReadout's L (hidden layers of the MLP)
    # edge slots per node of the graphs it is deployed on
    # (``serve.gatedgcn_deployment`` builds k-NN lists of this k): the
    # exporter records it on the edge ops, whose cost the deployment
    # flow counts on E = in_edges · n rows. ``apply`` takes any list.
    in_edges: int = 8
    readout: str = "node"      # 'node' (classification) | 'graph'
    # D, E and B are linear, so they can run on the N nodes and be
    # gathered to the E edges (DGL's ``u_add_v``, the published form and
    # the one ``to_graph`` exports) instead of running on the gathered
    # endpoints: 3·N·d² MACs in place of 3·E·d². Mathematically
    # identical (tested).
    transform_then_gather: bool = False


def readout_dims(cfg: GatedGCNConfig) -> list[tuple[int, int]]:
    """(fan_in, fan_out) of each readout matrix: d → d/2 → … → classes."""
    d = [cfg.d_hidden // 2 ** i for i in range(cfg.readout_layers + 1)]
    return list(zip(d, d[1:])) + [(d[-1], cfg.n_classes)]


def _bn_init(d):
    return {"gamma": jnp.ones((d,), jnp.float32),
            "beta": jnp.zeros((d,), jnp.float32),
            "mean": jnp.zeros((d,), jnp.float32),
            "var": jnp.ones((d,), jnp.float32)}


def init(key, cfg: GatedGCNConfig):
    """Random weights; batch-norms at γ = 1, β = 0 and running
    statistics μ = 0, σ² = 1 (see ``set_running_stats``)."""
    dh = cfg.d_hidden
    ks = jax.random.split(key, 3 + len(_LAYER_MATS) * cfg.n_layers)
    ro = readout_dims(cfg)
    p = {
        "embed_h": dense_init(ks[0], cfg.d_in, dh),
        "embed_e": dense_init(ks[1], cfg.d_edge_in, dh),
        "readout": [dense_init(k, a, b) for k, (a, b)
                    in zip(jax.random.split(ks[2], len(ro)), ro)],
        "layers": [],
    }
    for i in range(cfg.n_layers):
        base = 3 + len(_LAYER_MATS) * i
        lp = {m: dense_init(ks[base + j], dh, dh)
              for j, m in enumerate(_LAYER_MATS)}
        lp["bn_h"], lp["bn_e"] = _bn_init(dh), _bn_init(dh)
        p["layers"].append(lp)
    return p


PARAM_RULES = [
    (r"embed_h/w", P(DP, TP)),
    (r"layers/.*/w", P(DP, TP)),
    (r"readout/.*/w", P(DP, None)),
]


def batchnorm_eval(bn, x, mask):
    """Inference-mode batch-norm from running statistics, zero on the
    rows ``mask`` leaves out."""
    scale = bn["gamma"] * jax.lax.rsqrt(bn["var"] + BN_EPS)
    return ((x - bn["mean"]) * scale + bn["beta"]) * mask[..., None]


def _batchnorm(bn, x, mask, train):
    if train:       # batch statistics over the real rows
        return (C.masked_batchnorm(x, mask, eps=BN_EPS) * bn["gamma"]
                + bn["beta"]) * mask[:, None]
    return batchnorm_eval(bn, x, mask)


def _pre_norm(lp, h, e, graph, cfg):
    """One layer up to its batch-norms: the node update's argument
    ``A h_i + gate`` and the edge logits ``ê``."""
    ei, em = graph["edge_index"], graph["edge_mask"]
    n = h.shape[0]
    if cfg.transform_then_gather:
        dj = jnp.take(dense_apply(lp["D"], h), ei[0], axis=0)
        ei_ = jnp.take(dense_apply(lp["E"], h), ei[1], axis=0)
        bj = jnp.take(dense_apply(lp["B"], h), ei[0], axis=0)
    else:           # per-edge dense on the gathered endpoints
        hj, hi = C.gather_src(h, ei), C.gather_dst(h, ei)
        dj, ei_, bj = (dense_apply(lp["D"], hj), dense_apply(lp["E"], hi),
                       dense_apply(lp["B"], hj))
    ehat = dj + ei_ + dense_apply(lp["C"], e)
    sig = jax.nn.sigmoid(ehat) * em[:, None]
    num = C.scatter_sum(sig * bj, ei, n)
    den = C.scatter_sum(sig, ei, n)
    return dense_apply(lp["A"], h) + num / (den + GATE_EPS), ehat


def _embed(params, graph, cfg):
    h = dense_apply(params["embed_h"], graph["nodes"])
    e = dense_apply(params["embed_e"], graph.get(
        "edges", jnp.ones((graph["edge_index"].shape[1], cfg.d_edge_in),
                          h.dtype)))
    return h, e


def _readout(params, x):
    *hidden, last = params["readout"]
    for p in hidden:
        x = jax.nn.relu(dense_apply(p, x))
    return dense_apply(last, x)


def apply(params, graph, cfg: GatedGCNConfig, *, train: bool = False):
    """Per-node logits (per-graph with ``readout='graph'``) of one
    graph. ``train`` normalises by the batch statistics of the graph's
    real rows; the default uses the running statistics."""
    nm, em = graph["node_mask"], graph["edge_mask"]
    h, e = _embed(params, graph, cfg)
    for lp in params["layers"]:
        pre, ehat = _pre_norm(lp, h, e, graph, cfg)
        h = h + jax.nn.relu(_batchnorm(lp["bn_h"], pre, nm, train))
        e = e + jax.nn.relu(_batchnorm(lp["bn_e"], ehat, em, train))
    if cfg.readout == "graph":
        h = (h * nm[:, None]).sum(0) / jnp.maximum(nm.sum(), 1.0)
    return _readout(params, h)


def _pooled(x, mask):
    """Mean and (biased) variance per channel over the rows of every
    graph that ``mask`` keeps."""
    m = mask[..., None]
    n = jnp.maximum(m.sum(), 1.0)
    mu = (x * m).sum(axis=(0, 1)) / n
    var = (((x - mu) ** 2) * m).sum(axis=(0, 1)) / n
    return mu, var


def set_running_stats(params, graphs, cfg: GatedGCNConfig):
    """``params`` with every batch-norm's running mean and variance set
    from the calibration ``graphs`` (feeds stacked on a leading event
    axis): layer by layer, each BN's statistics are pooled over the
    real nodes (or real edges) of all the graphs, computed with the
    statistics already set in the layers before it. This stands in for
    trained running statistics, and keeps the residual stream of a
    deep random network at the scale its batch-norms expect."""
    g = {k: jnp.asarray(v) for k, v in graphs.items()}
    h, e = jax.vmap(lambda gr: _embed(params, gr, cfg))(g)
    layers = []
    for lp in params["layers"]:
        pre, ehat = jax.vmap(lambda hh, ee, gr: _pre_norm(lp, hh, ee, gr,
                                                          cfg))(h, e, g)
        lp = dict(lp)
        for key, x, mask in (("bn_h", pre, g["node_mask"]),
                             ("bn_e", ehat, g["edge_mask"])):
            mu, var = _pooled(x, mask)
            lp[key] = {**lp[key], "mean": mu, "var": var}
        h = h + jax.nn.relu(batchnorm_eval(lp["bn_h"], pre, g["node_mask"]))
        e = e + jax.nn.relu(batchnorm_eval(lp["bn_e"], ehat, g["edge_mask"]))
        layers.append(lp)
    return {**params, "layers": layers}


def to_graph(params, cfg: GatedGCNConfig):
    """Export the inference network as a dataflow graph for the
    deployment flow (repro.core.pipeline).

    Every layer expands into the edge-typed IR ops the pattern-keyed
    passes dispatch on: the four node matrices A, B, D, E on the nodes
    (fusion merges them into one matmul) and ``gather_edge`` of B, D to
    the sources and of E to the destinations, C on the edges,
    ``eltwise`` gate algebra, two ``edge_aggregate`` segment sums (the
    Pallas one-hot-incidence kernel) and two inference-mode
    ``batchnorm`` ops. Op names are ``l{i}_*`` per layer, ``embed_*``
    and ``readout*``. Only ``readout='node'`` deploys — graph pooling
    has no IR op yet."""
    if cfg.readout != "node":
        raise ValueError(
            f"gatedgcn export supports readout='node' only, "
            f"got {cfg.readout!r}")
    g = Graph()
    dh = cfg.d_hidden

    def lin(name, inp, p, d_out):
        g.add(Operator(name=name, op_type="linear", inputs=[inp],
                       params=dict(p), out_dim=d_out))
        return name

    def relu(name, inp, d):
        g.add(Operator(name=name, op_type="relu", inputs=[inp], out_dim=d))
        return name

    def elt(name, fn, inputs, d, **extra):
        g.add(Operator(name=name, op_type="eltwise", inputs=list(inputs),
                       attrs={"fn": fn, **extra}, out_dim=d))
        return name

    def gather(name, inp, endpoint):
        g.add(Operator(name=name, op_type="gather_edge",
                       inputs=[inp, "edge_index"],
                       attrs={"endpoint": endpoint,
                              "in_edges": cfg.in_edges}, out_dim=dh))
        return name

    def segsum(name, inp):
        g.add(Operator(name=name, op_type="edge_aggregate",
                       inputs=[inp, "edge_index"],
                       attrs={"reduce": "sum", "in_edges": cfg.in_edges},
                       out_dim=dh))
        return name

    def bn(name, inp, mask, p):
        g.add(Operator(name=name, op_type="batchnorm", inputs=[inp, mask],
                       params=dict(p), attrs={"eps": BN_EPS}, out_dim=dh))
        return name

    for feat, d in (("nodes", cfg.d_in), ("edge_index", 2),
                    ("edges", cfg.d_edge_in), ("node_mask", 1),
                    ("edge_mask", 1)):
        g.add(Operator(name=feat, op_type="input", out_dim=d,
                       attrs={"feature": feat}))
    h = lin("embed_h", "nodes", params["embed_h"], dh)
    e = lin("embed_e", "edges", params["embed_e"], dh)
    for i, lp in enumerate(params["layers"]):
        a, b, d, e_ = (lin(f"l{i}_{m}", h, lp[m], dh) for m in "ABDE")
        ehat = elt(f"l{i}_ehat", "add",
                   [gather(f"l{i}_Dj", d, "src"),
                    gather(f"l{i}_Ei", e_, "dst"),
                    lin(f"l{i}_C", e, lp["C"], dh)], dh)
        sig = elt(f"l{i}_sigm", "mask",
                  [elt(f"l{i}_sig", "sigmoid", [ehat], dh), "edge_mask"],
                  dh)
        num = segsum(f"l{i}_agg",
                     elt(f"l{i}_msg", "mul",
                         [sig, gather(f"l{i}_Bj", b, "src")], dh))
        den = elt(f"l{i}_den_eps", "add_const",
                  [segsum(f"l{i}_den", sig)], dh, const=GATE_EPS)
        pre = elt(f"l{i}_pre", "add",
                  [a, elt(f"l{i}_gate", "div", [num, den], dh)], dh)
        hbn = relu(f"l{i}_hbn_relu",
                   bn(f"l{i}_hbn", pre, "node_mask", lp["bn_h"]), dh)
        ebn = relu(f"l{i}_ebn_relu",
                   bn(f"l{i}_ebn", ehat, "edge_mask", lp["bn_e"]), dh)
        h = elt(f"l{i}_h", "add", [h, hbn], dh)
        e = elt(f"l{i}_e", "add", [e, ebn], dh)
    x = h
    ro = params["readout"]
    for j, p in enumerate(ro):
        x = lin(f"readout{j}", x, p, int(p["w"].shape[1]))
        if j < len(ro) - 1:
            x = relu(f"readout{j}_relu", x, int(p["w"].shape[1]))
    g.add(Operator(name="out", op_type="output", inputs=[x],
                   attrs={"head_names": ["logits"]},
                   out_dim=cfg.n_classes))
    g.validate()
    g.meta["config"] = cfg
    return g


def loss_fn(params, graph, cfg: GatedGCNConfig):
    logits = apply(params, graph, cfg, train=True)
    labels = graph["labels"]
    if cfg.readout == "graph":     # graph-level classification (scalar
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))  # label)
        loss = -logp[labels]
        acc = (logits.argmax(-1) == labels).astype(jnp.float32)
        return loss, {"loss": loss, "acc": acc}
    nm = graph["node_mask"] * graph.get("train_mask",
                                        jnp.ones_like(graph["node_mask"]))
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ce = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    loss = (ce * nm).sum() / jnp.maximum(nm.sum(), 1.0)
    acc = ((logits.argmax(-1) == labels) * nm).sum() / \
        jnp.maximum(nm.sum(), 1.0)
    return loss, {"loss": loss, "acc": acc}


register_exporter("gatedgcn", to_graph)
