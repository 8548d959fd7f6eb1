"""Plain reference of a small GraphSAGE (Hamilton et al.,
arXiv:1706.02216) for the harness tests, in straightforward
``jax.numpy``; it imports nothing of the program under test.

Full-graph mode: per layer, the mean of the edges' source features at
each destination (masked edges left out), a Dense with ReLU on the
concatenation of a node's features and that mean, L2 normalisation
and the node mask; a linear head gives per-node class logits. ``w`` is
a configuration's ``widths`` (``n_layers``, ``d_hidden``, ``d_in``,
``n_classes``, ``normalize``). There is no decision and no k-NN
selection, so the comparison reads the logits alone.

The events are random graphs made here from the seed: ``n_hits``
nodes of ``node_features`` normal features, ``n_edges`` edges with
endpoints drawn uniformly, 80% of the nodes and 70% of the edges real.
With ``control`` every dense matmul runs as three bfloat16 products.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def pool(det, n, seed):
    """``n`` random graphs from ``seed``: the feeds, each graph's real
    node count, and the arrays the comparison reads (the feeds)."""
    rng = np.random.default_rng(seed)
    nn, ne = det["n_hits"], det["n_edges"]
    g = {"nodes": rng.normal(size=(n, nn, det["node_features"]))
         .astype(np.float32),
         "edge_index": rng.integers(0, nn, size=(n, 2, ne)).astype(np.int32),
         "node_mask": (rng.uniform(size=(n, nn)) < 0.8).astype(np.float32),
         "edge_mask": (rng.uniform(size=(n, ne)) < 0.7).astype(np.float32)}
    return {"feeds": g, "rows": g["node_mask"].sum(1).astype(np.int64),
            "data": g}


def _shapes(w):
    d = [w["d_in"]] + [w["d_hidden"]] * w["n_layers"]
    return [(2 * a, b) for a, b in zip(d, d[1:])] + [(w["d_hidden"],
                                                     w["n_classes"])]


def weights(seed, w, gain, calib):
    """In the program's layout, from ``seed`` in one jitted call:
    truncated normal of standard deviation ``gain / sqrt(fan_in)``,
    zero biases. ``calib`` is not read."""
    key = jax.random.key(int(np.random.default_rng(seed).integers(2**31)))
    shapes = _shapes(w)

    @jax.jit
    def make(key):
        ps = [{"w": jax.random.truncated_normal(k, -2.0, 2.0, s)
               * np.float32(gain / np.sqrt(s[0])),
               "b": jnp.zeros((s[1],), jnp.float32)}
              for k, s in zip(jax.random.split(key, len(shapes)), shapes)]
        return {"layers": [{"w": p} for p in ps[:-1]], "head": ps[-1]}
    return make(key)


def _bf16_3x(a, b):
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


def _one(p, g, normalize, control):
    h, (src, dst) = g["nodes"], g["edge_index"]
    em, n = g["edge_mask"], g["nodes"].shape[0]
    cnt = jnp.zeros((n,), jnp.float32).at[dst].add(em)
    for lp in p["layers"]:
        msum = jnp.zeros((n, h.shape[1]), jnp.float32).at[dst].add(
            h[src] * em[:, None])
        neigh = msum / jnp.maximum(cnt, 1.0)[:, None]
        z = jax.nn.relu(_dense(lp["w"], jnp.concatenate([h, neigh], -1),
                               control))
        if normalize:
            z = z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True),
                                1e-6)
        h = z * g["node_mask"][:, None]
    return _dense(p["head"], h, control)


@functools.partial(jax.jit, static_argnames=("normalize", "control"))
def _run(p, g, normalize, control):
    return jax.vmap(lambda e: _one(p, e, normalize, control))(g)


def expected(p, data, w, *, control=False):
    """Per-node logits and the node mask of the graphs ``data``."""
    logits = _run(p, {k: jnp.asarray(v) for k, v in data.items()},
                  bool(w["normalize"]), control)
    return {"heads": {"logits": np.asarray(jax.device_get(logits))},
            "mask": data["node_mask"]}


def served(out):
    return {"heads": {"logits": out["logits"]}}


def model_flops(rows, w):
    """The dense layers and the head on ``rows`` real nodes; the
    aggregation and the normalisation are not counted."""
    return sum(2 * rows * a * b for a, b in _shapes(w))
