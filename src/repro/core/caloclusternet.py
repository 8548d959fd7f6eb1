"""CaloClusterNet — the dynamic GNN the paper deploys (refs [10]/[14]).

Per event: up to ``n_hits`` non-zero sparse calorimeter hits (of
``n_crystals`` crystals; 128/8736 post-upgrade, 32/576 current detector),
each with features (energy, θ, φ, t). The network is GravNet-based
(Qasim et al. 1902.07987) with object-condensation outputs
(Kieseler 2002.03605):

  encoder Dense×2 → [GravNet block]×2 → decoder Dense×2 →
  per-hit heads: β, cluster coords (2), energy, class logits (3)
  → CPS (condensation point selection) → ≤ k_max clusters + trigger bit.

Two synchronized forms:
- ``init/apply``: functional, differentiable (training path, jnp ref ops);
- ``to_graph``: the dataflow-IR export consumed by the deployment flow
  (repro.core.pipeline) — numerically identical in fp mode (tested).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.graph_ir import Graph, Operator, register_exporter
from repro.kernels import ref as kref
from repro.nn import dense_init, dense_apply


@dataclasses.dataclass(frozen=True)
class CCNConfig:
    n_hits: int = 128           # max nonzero inputs per event (upgrade)
    n_crystals: int = 8736
    d_in: int = 4               # (E, theta, phi, t)
    d_hidden: int = 64
    n_gravnet_blocks: int = 2
    d_s: int = 4                # learned spatial dims
    d_flr: int = 22             # learned feature dims
    k: int = 8                  # neighbors
    potential_scale: float = 10.0
    d_decoder: int = 32
    n_classes: int = 3          # photon / hadron / beam-background
    k_max: int = 8              # max condensation points per event
    t_beta: float = 0.3
    t_dist: float = 0.5         # min distance between condensation points
    e_trigger: float = 0.1      # GeV threshold on cluster energy
    gravnet_impl: str = "topk"  # 'topk' (gather) | 'onehot' (MXU-native)
    compute_dtype: str = "f32"  # 'f32' | 'bf16' (serving activations)

    @property
    def head_dims(self):
        # beta, coords(2), energy, class logits
        return {"beta": 1, "coords": 2, "energy": 1,
                "cls": self.n_classes}


def current_detector_config() -> CCNConfig:
    return dataclasses.replace(CCNConfig(), n_hits=32, n_crystals=576)


# ------------------------------------------------------------------ init ----
def init(key, cfg: CCNConfig):
    ks = jax.random.split(key, 16)
    p = {}
    p["enc1"] = dense_init(ks[0], cfg.d_in, cfg.d_hidden)
    p["enc2"] = dense_init(ks[1], cfg.d_hidden, cfg.d_hidden)
    for i in range(cfg.n_gravnet_blocks):
        p[f"gn{i}_s"] = dense_init(ks[2 + 3 * i], cfg.d_hidden, cfg.d_s)
        p[f"gn{i}_flr"] = dense_init(ks[3 + 3 * i], cfg.d_hidden, cfg.d_flr)
        p[f"gn{i}_out"] = dense_init(ks[4 + 3 * i],
                                     cfg.d_hidden + 2 * cfg.d_flr,
                                     cfg.d_hidden)
    p["dec1"] = dense_init(ks[10], cfg.d_hidden, cfg.d_hidden)
    p["dec2"] = dense_init(ks[11], cfg.d_hidden, cfg.d_decoder)
    for j, (h, d) in enumerate(cfg.head_dims.items()):
        p[f"head_{h}"] = dense_init(ks[12 + j], cfg.d_decoder, d)
    return p


# ----------------------------------------------------------------- apply ----
def apply(params, feats, mask, cfg: CCNConfig):
    """feats: (B, N, d_in), mask: (B, N) -> per-hit output dict.

    Differentiable; uses the jnp reference ops (kernels/ref.py).
    """
    if cfg.compute_dtype == "bf16":
        feats = feats.astype(jnp.bfloat16)
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), params)
    x = dense_apply(params["enc1"], feats, activation=jax.nn.relu)
    x = dense_apply(params["enc2"], x, activation=jax.nn.relu)
    gn_ref = (kref.gravnet_aggregate_onehot_ref
              if cfg.gravnet_impl == "onehot"
              else kref.gravnet_aggregate_ref)
    agg_fn = jax.vmap(
        lambda s, f, m: gn_ref(
            s, f, m, k=cfg.k, scale=cfg.potential_scale))
    for i in range(cfg.n_gravnet_blocks):
        s = dense_apply(params[f"gn{i}_s"], x)
        flr = dense_apply(params[f"gn{i}_flr"], x)
        agg = agg_fn(s, flr, mask)
        x = dense_apply(params[f"gn{i}_out"],
                        jnp.concatenate([x, agg], axis=-1),
                        activation=jax.nn.relu)
    x = dense_apply(params["dec1"], x, activation=jax.nn.relu)
    x = dense_apply(params["dec2"], x, activation=jax.nn.relu)
    out = {h: dense_apply(params[f"head_{h}"], x)
           for h in cfg.head_dims}
    return {
        "beta_logit": out["beta"][..., 0],
        "coords": out["coords"],
        "energy": out["energy"][..., 0],
        "cls_logits": out["cls"],
    }


# ------------------------------------------------------------------- CPS ----
def _best_candidate(cand, beta, x, y, energy):
    """Per event, the candidate with the highest β, the lowest index
    among equal β: one reduction over the hits that carries the winner's
    coordinates and energy along, so nothing is gathered. Returns the
    winner's ``(beta, x, y, energy)``; where no candidate is left its β
    is -inf."""
    key = jnp.where(cand, beta, -jnp.inf)
    idx = jax.lax.broadcasted_iota(jnp.int32, beta.shape, 1)

    def better(a, b):
        take_a = (a[0] > b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))
        return tuple(jnp.where(take_a, u, v) for u, v in zip(a, b))

    init = (jnp.float32(-jnp.inf), jnp.int32(beta.shape[1]),
            jnp.float32(0), jnp.float32(0), jnp.float32(0))
    b, _, bx, by, be = jax.lax.reduce((key, idx, x, y, energy), init,
                                      better, (1,))
    return b, bx, by, be


def cps(outputs, mask, cfg: CCNConfig):
    """Condensation Point Selection over a batch of events.

    Greedy over hits in decreasing β: select hits with β > t_beta that are
    more than t_dist away (in learned cluster-coordinate space) from every
    already-selected point; at most k_max points.

    Run as ``k_max`` rounds of non-maximum suppression, unrolled: round j
    takes each event's highest-β surviving candidate (the lowest index
    among equal β, the order a stable sort by decreasing β visits them
    in), writes it to slot j, and drops every candidate within t_dist of
    it, itself included. The hit the greedy scan accepts j-th is the
    first in β order that is above t_beta and far from the j picks before
    it, which is exactly that round's winner, and a squared distance is
    the same two squares summed, so the result is the scan's bit for
    bit. Once no candidate is left every later round finds none either.
    Fixed shapes throughout, k_max steps over whole events instead of
    one step per hit, no sort and no per-hit state update (the paper
    runs this on FPGA fabric; here it is the canonical 'irregular' op
    pinned to the XLA partition).
    """
    beta = (jax.nn.sigmoid(outputs["beta_logit"].astype(jnp.float32))
            * mask.astype(jnp.float32))
    coords = outputs["coords"].astype(jnp.float32)
    x, y = coords[..., 0], coords[..., 1]
    energy = outputs["energy"].astype(jnp.float32)
    cand = beta > cfg.t_beta
    picks = []
    for _ in range(cfg.k_max):
        b, px, py, e = _best_candidate(cand, beta, x, y, energy)
        picks.append((b, px, py, e))
        d2 = (x - px[:, None]) ** 2 + (y - py[:, None]) ** 2
        cand = cand & (d2 > cfg.t_dist ** 2)
    sel_b, sel_x, sel_y, sel_e = (jnp.stack(v, axis=1) for v in zip(*picks))
    valid = sel_b > -jnp.inf
    sel_b, sel_e = (jnp.where(valid, v, 0.0) for v in (sel_b, sel_e))
    sel_xy = jnp.where(valid[..., None], jnp.stack([sel_x, sel_y], -1), 0.0)
    return {"cluster_xy": sel_xy, "cluster_e": sel_e,
            "cluster_beta": sel_b, "cluster_valid": valid,
            "n_clusters": jnp.sum(valid, axis=1, dtype=jnp.int32),
            "trigger": jnp.any(valid & (sel_e > cfg.e_trigger), axis=1)}


# -------------------------------------------------------------- IR export ----
def to_graph(params, cfg: CCNConfig) -> Graph:
    """Export as a dataflow graph for the deployment flow.

    Every layer is one operator; GravNet blocks expand to
    (linear_s ∥ linear_flr) → gravnet_aggregate → concat → linear → relu,
    exposing exactly the fusion opportunities the paper exploits."""
    g = Graph()

    def lin(name, inp, d_out):
        g.add(Operator(name=name, op_type="linear", inputs=[inp],
                       params=dict(params[name]), out_dim=d_out))
        return name

    def relu(name, inp, d):
        g.add(Operator(name=name, op_type="relu", inputs=[inp], out_dim=d))
        return name

    g.add(Operator(name="hits", op_type="input", out_dim=cfg.d_in,
                   attrs={"feature": "hits"}))
    g.add(Operator(name="mask", op_type="input", out_dim=1,
                   attrs={"feature": "mask"}))
    x = relu("enc1_relu", lin("enc1", "hits", cfg.d_hidden), cfg.d_hidden)
    x = relu("enc2_relu", lin("enc2", x, cfg.d_hidden), cfg.d_hidden)
    for i in range(cfg.n_gravnet_blocks):
        s = lin(f"gn{i}_s", x, cfg.d_s)
        f = lin(f"gn{i}_flr", x, cfg.d_flr)
        agg = f"gn{i}_agg"
        g.add(Operator(name=agg, op_type="gravnet_aggregate",
                       inputs=[s, f, "mask"],
                       attrs={"k": cfg.k, "scale": cfg.potential_scale,
                              "d_s": cfg.d_s, "d_f": cfg.d_flr},
                       out_dim=2 * cfg.d_flr))
        cat = f"gn{i}_cat"
        g.add(Operator(name=cat, op_type="concat", inputs=[x, agg],
                       out_dim=cfg.d_hidden + 2 * cfg.d_flr))
        x = relu(f"gn{i}_out_relu", lin(f"gn{i}_out", cat, cfg.d_hidden),
                 cfg.d_hidden)
    x = relu("dec1_relu", lin("dec1", x, cfg.d_hidden), cfg.d_hidden)
    x = relu("dec2_relu", lin("dec2", x, cfg.d_decoder), cfg.d_decoder)
    heads = []
    for h, d in cfg.head_dims.items():
        heads.append(lin(f"head_{h}", x, d))
    g.add(Operator(name="cps", op_type="cps",
                   inputs=heads + ["mask"],
                   attrs={"k_max": cfg.k_max, "t_beta": cfg.t_beta,
                          "t_dist": cfg.t_dist, "e_trigger": cfg.e_trigger,
                          "head_names": list(cfg.head_dims)},
                   out_dim=cfg.k_max))
    g.add(Operator(name="out", op_type="output",
                   inputs=heads + ["cps"],
                   attrs={"head_names": list(cfg.head_dims)},
                   out_dim=sum(cfg.head_dims.values())))
    g.validate()
    g.meta["config"] = cfg
    return g


register_exporter("caloclusternet", to_graph)
