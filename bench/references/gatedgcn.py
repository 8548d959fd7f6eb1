"""Plain reference of GatedGCN (Bresson & Laurent, arXiv:1711.07553) in
the benchmarking-gnns form (arXiv:2003.00982), in straightforward
float32 ``jax.numpy``. It imports nothing of the program under test.

Per layer, with i the destination and j the source of an edge, the
gate multiplied by the edge mask so that padded edges add nothing:

    ê_ij = D h_j + E h_i + C e_ij          σ_ij = sigmoid(ê_ij) · mask_ij
    h_i' = h_i + ReLU(BN_h(A h_i + Σ_j σ_ij ⊙ B h_j / (Σ_j σ_ij + 1e-6)))
    e_ij' = e_ij + ReLU(BN_e(ê_ij))
    BN(x) = γ (x − μ_run) / sqrt(σ²_run + 1e-5) + β, zero on padded rows

``h⁰ = Linear(d_in → d)(hits)``, ``e⁰ = Linear(d_edge_in → d)(edges)``,
and the readout is the MLPReadout ``d → d/2 → … → d/2^L → n_classes``
with ReLU between. Every matrix has a bias. ``w`` is a configuration's
``widths`` (``n_layers``, ``d_hidden``, ``d_in``, ``d_edge_in``,
``n_classes``, ``readout_layers``, ``in_edges``).

The graphs are hit events of the benchmark's generator
(``bench/events.py``): the hits are the nodes, and each real hit gets
an edge from each of its ``in_edges`` nearest other real hits in
(θ, φ) (squared distance in float64 from the float32 features, an
exact tie going to the lower row index), whose squared distance is the
edge's one feature; edge slots without an edge point at row 0 with
mask 0. With ``control`` every dense matmul runs as three bfloat16
products. There is no decision and no k-NN selection on the device,
so the comparison reads the logits alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import events

BN_EPS = 1e-5
GATE_EPS = 1e-6


# ------------------------------------------------------------- graphs ----
def _edges_of(hits, mask, k):
    """One event's edge list, hit by hit."""
    n = mask.shape[0]
    src = np.zeros(n * k, np.int32)
    dst = np.zeros(n * k, np.int32)
    d2s = np.zeros((n * k, 1), np.float32)
    em = np.zeros(n * k, np.float32)
    th = hits[:, 1].astype(np.float64)
    ph = hits[:, 2].astype(np.float64)
    real = np.flatnonzero(mask > 0)
    for i in real:
        others = real[real != i]
        d2 = (th[others] - th[i]) ** 2 + (ph[others] - ph[i]) ** 2
        near = np.lexsort((others, d2))[:k]    # by distance, then index
        sl = slice(i * k, i * k + len(near))
        src[sl], dst[sl] = others[near], i
        d2s[sl, 0], em[sl] = d2[near], 1.0
    return np.stack([src, dst]), d2s, em


def pool(det, n, seed):
    """``n`` hit graphs of the detector ``det`` from ``seed``: the feeds
    (``nodes``, ``edge_index``, ``edges``, ``node_mask``,
    ``edge_mask``), each event's real hit count, and the arrays the
    comparison reads (the same feeds)."""
    ev = events.generate(det, n, seed)
    parts = [_edges_of(h, m, det["in_edges"])
             for h, m in zip(ev["hits"], ev["mask"])]
    g = {"nodes": ev["hits"],
         "edge_index": np.stack([p[0] for p in parts]),
         "edges": np.stack([p[1] for p in parts]),
         "node_mask": ev["mask"],
         "edge_mask": np.stack([p[2] for p in parts])}
    return {"feeds": g, "rows": ev["mask"].sum(1).astype(np.int64),
            "data": g}


# ------------------------------------------------------------ weights ----
def _readout_shapes(w):
    d = [w["d_hidden"] // 2 ** i for i in range(w["readout_layers"] + 1)]
    return list(zip(d, d[1:])) + [(d[-1], w["n_classes"])]


def _matrices(w):
    """(path, fan_in, fan_out) of every matrix, in the program's
    parameter layout."""
    dh = w["d_hidden"]
    out = [(("embed_h",), w["d_in"], dh), (("embed_e",), w["d_edge_in"], dh)]
    for i in range(w["n_layers"]):
        out += [(("layers", i, m), dh, dh) for m in "ABCDE"]
    out += [(("readout", j), a, b)
            for j, (a, b) in enumerate(_readout_shapes(w))]
    return out


def _bn(x, bn, m):
    return (bn["gamma"] * (x - bn["mean"]) / jnp.sqrt(bn["var"] + BN_EPS)
            + bn["beta"]) * m[:, None]


def weights(seed, w, gain, calib):
    """In the program's layout (``embed_h``, ``embed_e``, ``layers`` of
    ``A``..``E``, ``bn_h``, ``bn_e``, ``readout``), from ``seed`` in one
    jitted call: every weight and bias uniform in ±gain/sqrt(fan_in)
    (PyTorch's ``nn.Linear`` initialisation at gain 1), every BN at
    γ = 1 and β = 0. The running statistics are then set on the
    calibration graphs ``calib`` (a ``pool``), layer by layer: each
    BN's mean and biased variance over the real nodes (or edges) of all
    of them, with the layers before it already set."""
    key = jax.random.key(int(np.random.default_rng(seed).integers(2**31)))
    mats = _matrices(w)

    @jax.jit
    def make(key):
        out = []
        for k, (_, fi, fo) in zip(jax.random.split(key, len(mats)), mats):
            kw, kb = jax.random.split(k)
            bound = np.float32(gain / np.sqrt(fi))
            out.append({"w": jax.random.uniform(kw, (fi, fo), jnp.float32,
                                                -bound, bound),
                        "b": jax.random.uniform(kb, (fo,), jnp.float32,
                                                -bound, bound)})
        return out

    dh = w["d_hidden"]
    p = {"layers": [{} for _ in range(w["n_layers"])], "readout": []}
    for (path, _, _), m in zip(mats, make(key)):
        if path[0] == "layers":
            p["layers"][path[1]][path[2]] = m
        elif path[0] == "readout":
            p["readout"].append(m)
        else:
            p[path[0]] = m
    unit = {"gamma": jnp.ones((dh,)), "beta": jnp.zeros((dh,))}
    g = {k: jnp.asarray(v) for k, v in calib["data"].items()}
    with jax.default_matmul_precision("highest"):
        h = jax.vmap(lambda x: _dense(p["embed_h"], x, False))(g["nodes"])
        e = jax.vmap(lambda x: _dense(p["embed_e"], x, False))(g["edges"])
        for lp in p["layers"]:
            pre, ehat = jax.vmap(lambda hh, ee, ei, em: _pre_norm(
                lp, hh, ee, ei, em, False))(h, e, g["edge_index"],
                                            g["edge_mask"])
            for key_, x, m in (("bn_h", pre, g["node_mask"]),
                               ("bn_e", ehat, g["edge_mask"])):
                mm = m[..., None]
                cnt = jnp.sum(mm)
                mu = jnp.sum(x * mm, axis=(0, 1)) / cnt
                var = jnp.sum((x - mu) ** 2 * mm, axis=(0, 1)) / cnt
                lp[key_] = {**unit, "mean": mu, "var": var}
            h = h + jax.nn.relu(jax.vmap(
                lambda x, m: _bn(x, lp["bn_h"], m))(pre, g["node_mask"]))
            e = e + jax.nn.relu(jax.vmap(
                lambda x, m: _bn(x, lp["bn_e"], m))(ehat, g["edge_mask"]))
    return p


# ------------------------------------------------------------ forward ----
def _bf16_3x(a, b):
    """``a @ b`` as three bfloat16 products: hi*hi + hi*lo + lo*hi,
    the parts rounded with ``reduce_precision`` and multiplied exactly
    in float32."""
    def split(v):
        hi = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
        return hi, jax.lax.reduce_precision(v - hi, exponent_bits=8,
                                            mantissa_bits=7)

    def mm(u, v):
        return jnp.matmul(u, v, precision=jax.lax.Precision.HIGHEST)
    ah, al = split(a)
    bh, bl = split(b)
    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


def _dense(p, x, control):
    return (_bf16_3x(x, p["w"]) if control else x @ p["w"]) + p["b"]


def _pre_norm(lp, h, e, ei, em, control):
    """One event's layer up to its batch-norms: ``A h + gate`` and ê."""
    src, dst = ei[0], ei[1]
    n, d = h.shape
    ehat = (_dense(lp["D"], h, control)[src]
            + _dense(lp["E"], h, control)[dst] + _dense(lp["C"], e, control))
    sig = jax.nn.sigmoid(ehat) * em[:, None]
    num = jnp.zeros((n, d), jnp.float32).at[dst].add(
        sig * _dense(lp["B"], h, control)[src])
    den = jnp.zeros((n, d), jnp.float32).at[dst].add(sig)
    return _dense(lp["A"], h, control) + num / (den + GATE_EPS), ehat


def _one(p, g, control):
    nm, em = g["node_mask"], g["edge_mask"]
    h = _dense(p["embed_h"], g["nodes"], control)
    e = _dense(p["embed_e"], g["edges"], control)
    for lp in p["layers"]:
        pre, ehat = _pre_norm(lp, h, e, g["edge_index"], em, control)
        h = h + jax.nn.relu(_bn(pre, lp["bn_h"], nm))
        e = e + jax.nn.relu(_bn(ehat, lp["bn_e"], em))
    *hidden, last = p["readout"]
    for q in hidden:
        h = jax.nn.relu(_dense(q, h, control))
    return _dense(last, h, control)


@functools.partial(jax.jit, static_argnames=("control",))
def _run(p, g, control):
    return jax.vmap(lambda ev: _one(p, ev, control))(g)


def expected(p, data, w, *, control=False, block=256):
    """Per-hit logits and the hit mask of the graphs ``data``,
    ``block`` events per call; numpy out."""
    outs = []
    n = data["node_mask"].shape[0]
    for i in range(0, n, block):
        g = {k: jnp.asarray(v[i:i + block]) for k, v in data.items()}
        outs.append(np.asarray(jax.device_get(_run(p, g, control))))
    return {"heads": {"logits": np.concatenate(outs)},
            "mask": data["node_mask"]}


def served(out):
    return {"heads": {"logits": out["logits"]}}


def model_flops(rows, w):
    """Operations of one event with ``rows`` real hits and
    ``in_edges``·``rows`` edges: every matrix on the rows it runs on
    (A, B, D, E and the readout on the nodes, C on the edges), and the
    two segment sums of each layer, one add per edge and channel. The
    gate's sigmoid and division, the batch-norms and the residual adds
    are not counted."""
    d, n_e = w["d_hidden"], w["in_edges"] * rows
    f = 2 * rows * w["d_in"] * d + 2 * n_e * w["d_edge_in"] * d
    f += w["n_layers"] * (4 * 2 * rows * d * d + 2 * n_e * d * d
                          + 2 * n_e * d)
    f += sum(2 * rows * a * b for a, b in _readout_shapes(w))
    return f
