"""Plain reference of CaloClusterNet (GravNet blocks, object-condensation
heads, condensation-point selection), in straightforward ``jax.numpy``.

It imports nothing of the program under test. The layer equations are
the published ones (encoder Dense x2, GravNet blocks of S/F
projections, k-nearest Gaussian-potential mean and max aggregation and
an output Dense on the concatenation, decoder Dense x2, per-hit heads
beta, coords, energy and class logits; greedy selection of condensation
points by decreasing beta). ``w`` is a configuration's ``widths``.

``forward`` runs in float32 at the matmul precision the caller sets.
With ``control`` it stands in for a program one precision lower: every
dense matmul as three bfloat16 products (``high`` on a TPU, spelled
out so that any backend computes it). It also gives, per event, how
near the k-nearest selection came to a tie (``knn_margin``): the
smallest gap, over real hits and blocks, between the k-th and the
next distance, as a share of the event's largest squared coordinate.

The harness calls it through ``pool``, ``weights``, ``expected``,
``served`` and ``model_flops``, the functions every reference module
provides (``bench/run.py``, ``bench/check.py``): the events come from
the benchmark's copy of the generator (``bench/events.py``), the
operation count from ``bench/flops.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import events, flops

BIG = 1e30
HEADS = ("beta", "coords", "energy", "cls")


def head_dims(w):
    return dict(zip(HEADS, (1, 2, 1, w["n_classes"])))


def layers(w):
    """(name, fan_in, fan_out) of every dense layer, in order."""
    dh, dd = w["d_hidden"], w["d_decoder"]
    out = [("enc1", w["d_in"], dh), ("enc2", dh, dh)]
    for i in range(w["n_gravnet_blocks"]):
        out += [(f"gn{i}_s", dh, w["d_s"]), (f"gn{i}_flr", dh, w["d_flr"]),
                (f"gn{i}_out", dh + 2 * w["d_flr"], dh)]
    out += [("dec1", dh, dh), ("dec2", dh, dd)]
    out += [(f"head_{h}", dd, d) for h, d in head_dims(w).items()]
    return out


def init_params(seed: int, w, gain: float):
    """Weights from ``seed`` in one jitted call on the default device:
    truncated normal of standard deviation ``gain / sqrt(fan_in)`` and
    zero biases. ``gain`` keeps the activations of the small
    calorimeter features from fading layer by layer, so that the
    heads spread and events select several condensation points."""
    key = jax.random.key(int(np.random.default_rng(seed).integers(2**31)))
    spec = tuple(layers(w))

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(spec))
        return {name: {"w": jax.random.truncated_normal(k, -2.0, 2.0, (fi, fo))
                       * np.float32(gain / np.sqrt(fi)),
                       "b": jnp.zeros((fo,), jnp.float32)}
                for k, (name, fi, fo) in zip(keys, spec)}
    return make(key)


def place_threshold(p, hits, mask, w):
    """Set the energy head's bias so that half of the events ``hits``
    trigger. Selection does not read the energy head, so a bias ``b``
    triggers exactly the events whose most energetic selected point
    exceeds ``e_trigger - b``."""
    _, sel = forward(p, hits, mask, w)
    top = _top_energy(sel)
    b = w["e_trigger"] - float(np.median(top[np.isfinite(top)]))
    p = dict(p)
    p["head_energy"] = {"w": p["head_energy"]["w"],
                        "b": p["head_energy"]["b"] + jnp.float32(b)}
    return p


def _bf16_3x(a, b):
    """``a @ b`` as three bfloat16 products: hi*hi + hi*lo + lo*hi. The
    parts are rounded with ``reduce_precision``, which a compiler may
    not elide (a round trip through bfloat16 it may), and multiplied
    exactly in float32."""
    def split(v):
        hi = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(v - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi, lo

    def mm(u, v):
        return jnp.matmul(u, v, precision=jax.lax.Precision.HIGHEST)
    ah, al = split(a)
    bh, bl = split(b)
    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


def _dense(p, x, name, control, relu=False):
    wt = p[name]["w"]
    y = (_bf16_3x(x, wt) if control else x @ wt) + p[name]["b"]
    return jax.nn.relu(y) if relu else y


def aggregate(s, f, mask, k, scale):
    """One event: for every hit, its ``k`` nearest valid other hits in
    the learned space ``s``; features ``f`` weighted by exp(-scale d2),
    mean over the k slots and max over the valid ones; and how near the
    selection came to a tie (the module's ``knn_margin``)."""
    n = s.shape[0]
    d2 = jnp.sum((s[:, None, :] - s[None, :, :]) ** 2, axis=-1)
    bad = (mask[None, :] <= 0) | jnp.eye(n, dtype=bool)
    d2 = jnp.where(bad, BIG, d2)
    kk = min(k, n)
    neg, idx = jax.lax.top_k(-d2, min(k + 1, n))
    d2k, idx = -neg[:, :kk], idx[:, :kk]
    if n > k:       # a (k+1)-th valid hit that could swap with the k-th
        nxt = -neg[:, k]
        gap = jnp.where((mask > 0) & (nxt < BIG * 0.5), nxt - d2k[:, -1],
                        BIG)
        s2 = jnp.max(jnp.where(mask > 0, jnp.sum(s * s, axis=-1), 0.0))
        margin = jnp.min(gap) / jnp.maximum(s2, 1e-30)
    else:
        margin = jnp.float32(BIG)
    valid = d2k < BIG * 0.5
    wgt = jnp.where(valid, jnp.exp(-scale * d2k), 0.0)
    wf = wgt[..., None] * f[idx]
    mean = jnp.sum(jnp.where(valid[..., None], wf, 0.0), axis=1) / k
    mx = jnp.max(jnp.where(valid[..., None], wf, -BIG), axis=1)
    mx = jnp.where(mx <= -BIG * 0.5, 0.0, mx)
    return jnp.concatenate([mean, mx], axis=-1), margin


def _forward(p, hits, mask, w, control):
    x = _dense(p, hits, "enc1", control, relu=True)
    x = _dense(p, x, "enc2", control, relu=True)
    agg_fn = jax.vmap(functools.partial(aggregate, k=w["k"],
                                        scale=w["potential_scale"]))
    margin = jnp.full((hits.shape[0],), BIG, jnp.float32)
    for i in range(w["n_gravnet_blocks"]):
        s = _dense(p, x, f"gn{i}_s", control)
        f = _dense(p, x, f"gn{i}_flr", control)
        agg, m = agg_fn(s, f, mask)
        margin = jnp.minimum(margin, m)
        x = _dense(p, jnp.concatenate([x, agg], axis=-1), f"gn{i}_out",
                   control, relu=True)
    x = _dense(p, x, "dec1", control, relu=True)
    x = _dense(p, x, "dec2", control, relu=True)
    return {h: _dense(p, x, f"head_{h}", control)
            for h in head_dims(w)}, margin


def cps(heads, mask, w):
    """Greedy condensation-point selection per event: hits in
    decreasing beta; a hit with beta > t_beta at least t_dist from
    every selected point is selected, up to k_max; the event triggers
    when a selected point's energy exceeds e_trigger."""
    km = w["k_max"]

    def one(beta_logit, coords, energy, m):
        beta = jax.nn.sigmoid(beta_logit) * m
        order = jnp.argsort(-beta)

        def body(t, c):
            xy, e, b, n = c
            i = order[t]
            d2 = jnp.sum((xy - coords[i][None, :]) ** 2, axis=1)
            d2 = jnp.where(jnp.arange(km) < n, d2, BIG)
            ok = ((beta[i] > w["t_beta"]) & (jnp.min(d2) > w["t_dist"] ** 2)
                  & (n < km))
            xy = jnp.where(ok, xy.at[n].set(coords[i]), xy)
            e = jnp.where(ok, e.at[n].set(energy[i]), e)
            b = jnp.where(ok, b.at[n].set(beta[i]), b)
            return xy, e, b, n + ok.astype(jnp.int32)

        xy, e, b, n = jax.lax.fori_loop(
            0, beta.shape[0], body,
            (jnp.zeros((km, 2)), jnp.zeros((km,)), jnp.zeros((km,)),
             jnp.int32(0)))
        valid = jnp.arange(km) < n
        return {"n_clusters": n, "cluster_e": e,
                "trigger": jnp.any(valid & (e > w["e_trigger"]))}

    return jax.vmap(one)(heads["beta"][..., 0], heads["coords"],
                         heads["energy"][..., 0], mask)


@functools.partial(jax.jit, static_argnames=("wkey", "control"))
def _run(p, hits, mask, wkey, control):
    w = dict(wkey)
    heads, margin = _forward(p, hits, mask, w, control)
    return heads, {**cps(heads, mask, w), "knn_margin": margin}


def _key(w):
    return tuple(sorted(w.items()))


def forward(p, hits, mask, w, *, control=False, block=256):
    """Heads and selection (with ``knn_margin``) for a batch of events,
    ``block`` events per call; numpy out."""
    outs = []
    for i in range(0, hits.shape[0], block):
        outs.append(jax.device_get(_run(
            p, jnp.asarray(hits[i:i + block]), jnp.asarray(mask[i:i + block]),
            _key(w), control)))
    return jax.tree_util.tree_map(lambda *xs: np.concatenate(xs), *outs)


def _top_energy(sel):
    """Per event, the energy of its most energetic selected point
    (-inf where none was selected)."""
    km = sel["cluster_e"].shape[1]
    valid = np.arange(km)[None, :] < np.asarray(sel["n_clusters"])[:, None]
    return np.where(valid, sel["cluster_e"], -np.inf).max(axis=1)


# ------------------------------------------------------ the harness's seam ----
def pool(det, n, seed):
    """``n`` events of the detector ``det`` from ``seed``: the feeds
    (``hits``, ``mask``), each event's real hit count, and the arrays
    the comparison reads (the same hits and masks)."""
    ev = events.generate(det, n, seed)
    return {"feeds": ev, "rows": ev["mask"].sum(1).astype(np.int64),
            "data": ev}


def weights(seed, w, gain, calib):
    """The weights of ``init_params``, with the energy bias placed on
    the calibration events ``calib`` (a ``pool``)."""
    return place_threshold(init_params(seed, w, gain), calib["data"]["hits"],
                           calib["data"]["mask"], w)


def expected(p, data, w, *, control=False):
    """What the served trigger should give on the events ``data``: the
    heads, the hit mask, the trigger decision with its margin (the most
    energetic selected point's energy minus ``e_trigger``) and the
    events' k-NN tie margins; numpy out."""
    heads, sel = forward(p, data["hits"], data["mask"], w, control=control)
    return {"heads": heads, "mask": data["mask"],
            "decision": np.asarray(sel["trigger"], bool),
            "decision_margin": _top_energy(sel) - w["e_trigger"],
            "tie_margin": np.asarray(sel["knn_margin"])}


def served(out):
    """The heads and the trigger decision of one served event's
    outputs."""
    return {"heads": {h: out[h] for h in HEADS},
            "decision": bool(np.asarray(out["cps"]["trigger"]))}


def model_flops(rows, w):
    """Operations of one event with ``rows`` real hits."""
    return flops.model_flops(rows, w)
