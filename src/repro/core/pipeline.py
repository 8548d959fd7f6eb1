"""Deployment pipeline: run the flow's passes and emit an executable.

``deploy(graph, Requirements)`` mirrors the paper's design flow end-to-end
and supports the three evaluated design points:

  ① partitioned baseline — no fusion, P=1, looped kernels, one compiled
    executable *per pipeline segment* (each FPGA↔AIE boundary is a real
    dispatch boundary — reproducing the heterogeneous overhead that made
    design ① slower than the FPGA-only baseline);
  ② + operator fusion + spatial parallelization (P search);
  ③ + kernel-level optimizations (flattened kernels, retile cancellation,
    int8 chain fusion) and a single whole-pipeline executable.

Precision: 'mixed' applies the paper's policy (bf16 boundary segments,
int8 interior with per-channel weight scales and calibrated activation
scales); int8 matmuls use exact integer arithmetic (the same math the
Pallas int8 kernel executes on TPU — bit-agreement is tested).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import caloclusternet as ccn
from repro.core.graph_ir import Graph
from repro.core.passes.fusion import fuse
from repro.core.passes.kernel_opt import kernel_optimize
from repro.core.passes.mapping import LANE, map_templates
from repro.core.passes.parallelize import (Requirements, op_cost,
                                           parallelize, segment_time)
from repro.core.passes.partition import partition, segments
from repro.core.quantization import (activation_scale, apply_precision_policy,
                                     quantize_weight)
from repro.kernels import ops as kops
from repro.launch import mesh as hw


class QTensor(NamedTuple):
    """int8 activation + its (static) dequantization scale."""
    q: jax.Array
    scale: float


def _as_fp(v, dtype=jnp.float32):
    if isinstance(v, QTensor):
        return (v.q.astype(jnp.float32) * v.scale).astype(dtype)
    return v.astype(dtype)


def _pad_last(v, mult):
    d = v.shape[-1]
    r = (-d) % mult
    if r == 0:
        return v
    pw = [(0, 0)] * v.ndim
    pw[-1] = (0, r)
    return jnp.pad(v, pw)


# ---------------------------------------------------------------- executor ----
class _Executor:
    def __init__(self, graph: Graph, req: Requirements, backend: str):
        self.g = graph
        self.req = req
        self.backend = backend
        self.cfg = graph.meta.get("config")

    # -- single-op execution ------------------------------------------------
    def run_op(self, op, vals, feeds, *, force_fp=False, record=None):
        t = op.op_type
        prec = "fp" if force_fp else op.precision
        if t == "input":
            out = feeds[op.attrs["feature"]]
        elif t in ("dense", "linear"):
            out = self._dense(op, vals[0], prec)
        elif t == "relu":
            v = vals[0]
            out = (QTensor(jnp.maximum(v.q, 0), v.scale)
                   if isinstance(v, QTensor) else jnp.maximum(v, 0.0))
        elif t == "concat":
            if (all(isinstance(v, QTensor) for v in vals)
                    and len({v.scale for v in vals}) == 1):
                out = QTensor(jnp.concatenate([v.q for v in vals], -1),
                              vals[0].scale)
            else:
                out = jnp.concatenate([_as_fp(v) for v in vals], -1)
        elif t == "slice":
            st, sz = op.attrs["start"], op.attrs["size"]
            v = vals[0]
            if isinstance(v, QTensor):
                out = QTensor(v.q[..., st:st + sz], v.scale)
            else:
                out = v[..., st:st + sz]
        elif t == "retile":
            v = vals[0]
            if op.attrs["to"] == "lane128":
                out = (QTensor(_pad_last(v.q, LANE), v.scale)
                       if isinstance(v, QTensor) else _pad_last(v, LANE))
            else:
                d = op.out_dim
                out = (QTensor(v.q[..., :d], v.scale)
                       if isinstance(v, QTensor) else v[..., :d])
        elif t == "gravnet_aggregate":
            out = self._gravnet(op, vals, prec)
        elif t == "knn_build":
            out = self._knn_build(op, vals)
        elif t == "knn_aggregate":
            out = self._knn_aggregate(op, vals, prec)
        elif t == "gravnet_block":
            out = self._gravnet_block(op, vals, prec)
        elif t == "attention":
            out = self._attention(op, vals)
        elif t == "gather_edge":
            out = self._gather_edge(op, vals)
        elif t == "edge_aggregate":
            out = self._edge_aggregate(op, vals)
        elif t == "eltwise":
            out = self._eltwise(op, vals)
        elif t == "batchnorm":
            out = self._batchnorm(op, vals)
        elif t == "cps":
            out = self._cps(op, vals)
        elif t == "output":
            names = op.attrs["head_names"]
            out = {n: _as_fp(vals[i]) for i, n in enumerate(names)}
            if len(vals) > len(names):  # cps result dict
                out["cps"] = vals[len(names)]
        else:
            from repro.core.op_registry import op_spec
            hint = ("registered but not lowered by this executor"
                    if op_spec(t) is not None else "unknown op type")
            raise ValueError(f"no executor for op {op.name!r} "
                             f"({t!r}: {hint})")
        # knn_build's value is an (idx, d2) index tuple, not an
        # activation — nothing to record (and _as_fp would reject it)
        if record is not None and t not in ("cps", "output", "input",
                                            "knn_build"):
            record[op.name] = float(jnp.max(jnp.abs(_as_fp(out))))
        return out

    def _dense(self, op, x, prec):
        w = op.params["w"]
        b = op.params.get("b")
        act = op.attrs.get("activation", "none")
        variant = op.attrs_opt.get("variant", "looped")
        lead = None
        if prec == "int8" and "w_q" in (op.params or {}):
            if isinstance(x, QTensor):
                xq, in_scale = x.q, x.scale
            else:
                in_scale = op.attrs["in_scale"]
                xq = jnp.clip(jnp.round(x / in_scale), -127, 127
                              ).astype(jnp.int8)
            lead = xq.shape[:-1]
            xq2 = xq.reshape(-1, xq.shape[-1])
            wq, wscale = op.params["w_q"], op.params["w_scale"]
            if xq2.shape[-1] > wq.shape[0]:  # lane128-padded input
                wq = jnp.pad(wq, ((0, xq2.shape[-1] - wq.shape[0]), (0, 0)))
            emit8 = op.attrs_opt.get("emit_int8", False)
            out_scale = op.attrs.get("act_scale", 1.0)
            # autotuned block shapes bind here only when the config was
            # actually searched ('tuned'); the heuristic's fp-oriented
            # blocks never silently replace the int8 wrapper defaults
            blocks = {}
            if op.attrs_opt.get("tuned"):
                blocks = {"bm": op.attrs_opt.get("bm", 128),
                          "bn": op.attrs_opt.get("bn", 128),
                          "bk": op.attrs_opt.get("bk", 512)}
            y = kops.fused_dense_int8(
                xq2, wq, b, jnp.asarray(in_scale, jnp.float32).reshape(1, 1),
                wscale,
                activation=act, out_dtype=jnp.int8 if emit8 else jnp.float32,
                out_scale=out_scale, backend=self.backend, **blocks)
            y = y.reshape(*lead, y.shape[-1])
            return QTensor(y, out_scale) if emit8 else y
        # float path (fp/bf16 or uncalibrated int8 falls back to fp)
        dt = jnp.bfloat16 if prec == "bf16" else jnp.float32
        xf = _as_fp(x, dt)
        if xf.shape[-1] > w.shape[0]:   # lane128-padded input
            w = jnp.pad(w, ((0, xf.shape[-1] - w.shape[0]), (0, 0)))
        kw = dict(activation=act, variant=variant,
                  bm=op.attrs_opt.get("bm", 128),
                  bn=op.attrs_opt.get("bn", 128),
                  bk=op.attrs_opt.get("bk", 512), backend=self.backend)
        wd = w.astype(dt)
        bd = None if b is None else b.astype(dt)
        if xf.ndim == 3 and variant == "looped":
            # row-packs the micro-batch into the SAME (B·hits, d) looped
            # launch the autotuner times for this op's cache key
            return kops.fused_dense_batched(xf, wd, bd, **kw)
        # flattened stays row-packed into one whole-operand cell — the
        # problem shape the tuner measured; the grid-(B,) per-event form
        # is for callers wanting per-event cell residency (see
        # docs/kernels.md)
        lead = xf.shape[:-1]
        y = kops.fused_dense(xf.reshape(-1, xf.shape[-1]), wd, bd, **kw)
        return y.reshape(*lead, y.shape[-1])

    def _gravnet(self, op, vals, prec):
        s, f, mask = vals
        ds, df = op.attrs["d_s"], op.attrs["d_f"]
        sf = _as_fp(s)[..., :ds]
        ff = _as_fp(f)[..., :df]
        # one batched launch for the whole micro-batch (leading event
        # grid dim, per-event masking keeps selection block-diagonal)
        agg = kops.gravnet_aggregate_batched(
            sf, ff, mask, k=op.attrs["k"], scale=op.attrs["scale"],
            bm=op.attrs_opt.get("bm"), backend=self.backend)
        if prec == "int8" and "act_scale" in op.attrs:
            # model 8-bit FPGA-fabric arithmetic: snap to the int8 grid
            sc = op.attrs["act_scale"]
            agg = jnp.clip(jnp.round(agg / sc), -127, 127) * sc
        return agg

    def _knn_build(self, op, vals):
        """Ragged neighbor selection over bin-packed events: one
        batched launch per micro-batch of bins. Returns the (idx, d2)
        tuple the paired knn_aggregate consumes."""
        s, segids = vals
        sf = _as_fp(s)[..., :op.attrs["d_s"]]   # lane128-padded producer
        return kops.knn_build_batched(
            sf, segids.astype(jnp.int32), k=op.attrs["k"],
            bm=op.attrs_opt.get("bm"), backend=self.backend)

    def _knn_aggregate(self, op, vals, prec):
        f, knn = vals
        idx, d2 = knn
        ff = _as_fp(f)[..., :op.attrs["d_f"]]
        agg = kops.knn_aggregate_batched(
            ff, idx, d2, scale=op.attrs["scale"],
            bm=op.attrs_opt.get("bm"), backend=self.backend)
        if prec == "int8" and "act_scale" in op.attrs:
            # mirror gravnet_aggregate's 8-bit fabric arithmetic
            sc = op.attrs["act_scale"]
            agg = jnp.clip(jnp.round(agg / sc), -127, 127) * sc
        return agg

    def _gravnet_block(self, op, vals, prec="fp"):
        """One fused GravNet block — a single megakernel launch for the
        whole micro-batch. A calibrated int8 block (``ws_q`` present)
        launches the quantized megakernel with its baked scales; the fp
        path (and any uncalibrated int8 block) runs the f32 kernel."""
        x, mask = vals
        if op.attrs.get("ragged"):
            # raggedized block: the mask slot carries segment ids and
            # the launch covers a micro-batch of packed bins
            p = op.params
            xf = _as_fp(x)[..., :p["ws"].shape[0]]
            return kops.gravnet_block_ragged(
                xf, mask.astype(jnp.int32), p["ws"], p["bs"], p["wf"],
                p["bf"], p["wo"], p["bo"], k=op.attrs["k"],
                scale=op.attrs["scale"],
                activation=op.attrs.get("activation", "none"),
                concat_x=op.attrs.get("concat_x", True),
                bm=op.attrs_opt.get("bm"), backend=self.backend)
        p = op.params
        dh = p["ws"].shape[0]
        xf = _as_fp(x)[..., :dh]        # lane128-padded producer
        kw = {kn: op.attrs_opt[kn] for kn in ("bm", "bn", "bk")
              if kn in op.attrs_opt}
        if prec == "int8" and "ws_q" in p:
            # f32 in, f32 out: the kernel quantizes on entry with the
            # producer's calibrated scale and dequantizes the epilogue,
            # matching the unfused chain's boundary arithmetic exactly
            return kops.gravnet_block_int8_batched(
                xf, mask, p["ws_q"], p["bs"], p["wf_q"], p["bf"],
                p["wo_q"], p["bo"], p["ws_scale"], p["wf_scale"],
                p["wo_scale"], x_scale=op.attrs["in_scale"],
                agg_scale=op.attrs["agg_scale"],
                h_scale=op.attrs["h_scale"], k=op.attrs["k"],
                scale=op.attrs["scale"],
                activation=op.attrs.get("activation", "none"),
                concat_x=op.attrs.get("concat_x", True),
                backend=self.backend, **kw)
        return kops.gravnet_block_batched(
            xf, mask, p["ws"], p["bs"], p["wf"], p["bf"], p["wo"],
            p["bo"], k=op.attrs["k"], scale=op.attrs["scale"],
            activation=op.attrs.get("activation", "none"),
            concat_x=op.attrs.get("concat_x", True),
            backend=self.backend, **kw)

    def _gather_edge(self, op, vals):
        """Endpoint gather by the edge list: x:(B,N,d), ei:(B,2,E) ->
        (B,E,d). Data-dependent, so it stays on the xla target."""
        x, ei = vals
        d = op.out_dim
        xf = _as_fp(x)[..., :d]         # lane128-padded producer
        idx = ei[:, 0 if op.attrs["endpoint"] == "src" else 1, :]
        return jnp.take_along_axis(xf, idx[:, :, None].astype(jnp.int32),
                                   axis=1)

    def _edge_aggregate(self, op, vals):
        """Masked segment-sum/mean of per-edge messages into nodes —
        one batched one-hot-incidence kernel launch per micro-batch."""
        msgs, ei = vals[0], vals[1]
        mask = _as_fp(vals[2]) if len(vals) > 2 else None
        d = op.out_dim
        mf = _as_fp(msgs)[..., :d]
        n_nodes = int(op.attrs.get("n_nodes") or self.req.n_hits)
        return kops.edge_aggregate_batched(
            mf, ei.astype(jnp.int32), n_nodes, mask,
            reduce=op.attrs.get("reduce", "sum"),
            bm=op.attrs_opt.get("bm"), be=op.attrs_opt.get("be"),
            backend=self.backend)

    def _eltwise(self, op, vals):
        """N-ary elementwise algebra; ``fn`` picks the operation."""
        fn = op.attrs["fn"]
        d = op.out_dim
        if fn == "mask":                # x:(B,R,d) * mask:(B,R)
            x, m = _as_fp(vals[0])[..., :d], _as_fp(vals[1])
            return x * m[..., None]
        xs = [_as_fp(v)[..., :d] for v in vals]
        if fn == "add":
            y = xs[0]
            for v in xs[1:]:
                y = y + v
            return y
        if fn == "mul":
            y = xs[0]
            for v in xs[1:]:
                y = y * v
            return y
        if fn == "div":
            return xs[0] / xs[1]
        if fn == "sigmoid":
            return jax.nn.sigmoid(xs[0])
        if fn == "relu":
            return jnp.maximum(xs[0], 0.0)
        if fn == "add_const":
            return xs[0] + op.attrs["const"]
        if fn == "l2norm":
            return xs[0] / jnp.maximum(
                jnp.linalg.norm(xs[0], axis=-1, keepdims=True), 1e-6)
        raise ValueError(f"{op.name}: unknown eltwise fn {fn!r}")

    def _batchnorm(self, op, vals):
        """Masked per-event batch normalization (the benchmarking-gnns
        training-mode statistics, vectorized over the micro-batch):
        x:(B,R,d), mask:(B,R)."""
        x, mask = vals
        d = op.out_dim
        xf = _as_fp(x)[..., :d]
        m = _as_fp(mask)[..., None]
        n = jnp.maximum(m.sum(axis=1, keepdims=True), 1.0)
        mu = (xf * m).sum(axis=1, keepdims=True) / n
        var = (((xf - mu) ** 2) * m).sum(axis=1, keepdims=True) / n
        eps = op.attrs.get("eps", 1e-5)
        return (xf - mu) * jax.lax.rsqrt(var + eps) * m

    def _attention(self, op, vals):
        d = op.out_dim
        q, k_, v = (_as_fp(t)[..., :d] for t in vals)
        kw = {kn: op.attrs_opt[kn] for kn in ("bq", "bk")
              if kn in op.attrs_opt}
        return kops.flash_attention(q, k_, v,
                                    causal=op.attrs.get("causal", True),
                                    backend=self.backend, **kw)

    def _cps(self, op, vals):
        names = op.attrs["head_names"]
        hv = {n: _as_fp(vals[i]) for i, n in enumerate(names)}
        if op.attrs.get("ragged"):
            return self._cps_ragged(hv, vals[-2], vals[-1])
        mask = vals[-1]
        outputs = {
            "beta_logit": hv["beta"][..., 0],
            "coords": hv["coords"],
            "energy": hv["energy"][..., 0],
        }
        return ccn.cps(outputs, mask, self.cfg)

    def _cps_ragged(self, hv, segids, slots):
        """Scatter packed rows back to per-event (E, n_hits) layout,
        then run the unchanged per-event condensation. JAX *wraps*
        negative scatter indices even under ``mode="drop"``, so pad
        rows (segid −1) are first remapped to the out-of-bounds index
        ``e_max`` — which drop then discards."""
        e_max = int(self.g.meta["ragged_max_events"])
        n = self.req.n_hits
        seg = segids.reshape(-1).astype(jnp.int32)
        slot = slots.reshape(-1).astype(jnp.int32)
        seg = jnp.where(seg < 0, e_max, seg)

        def scatter(h):
            h2 = h.reshape(-1, *h.shape[2:])
            out = jnp.zeros((e_max, n, *h2.shape[1:]), h2.dtype)
            return out.at[seg, slot].set(h2, mode="drop")

        mask = jnp.zeros((e_max, n), jnp.float32
                         ).at[seg, slot].set(1.0, mode="drop")
        outputs = {
            "beta_logit": scatter(hv["beta"])[..., 0],
            "coords": scatter(hv["coords"]),
            "energy": scatter(hv["energy"])[..., 0],
        }
        return ccn.cps(outputs, mask, self.cfg)

    # -- full-graph execution -------------------------------------------------
    def run(self, feeds, *, force_fp=False, record=None):
        env: dict[str, Any] = {}
        result = None
        for op in self.g:
            vals = [env[i] for i in op.inputs]
            env[op.name] = self.run_op(op, vals, feeds, force_fp=force_fp,
                                       record=record)
            if op.op_type == "output":
                result = env[op.name]
        return result, env


# ---------------------------------------------------------- compiled object ----
class CompiledPipeline:
    def __init__(self, graph: Graph, req: Requirements, backend: str,
                 *, batch: int = 1):
        self.graph = graph
        self.req = req
        self.backend = backend
        self.segments = segments(graph)
        par = graph.meta.get("parallelization",
                             {"P_mxu": 1, "P_xla": 1, "microbatch": 1})
        # batch > 1 pins a *batch-packed* executable: the whole
        # micro-batch runs through every segment in one launch (no
        # P-chunking), matching the batched kernel grid shapes that
        # kernel_optimize(batch=...) keyed the tuning cache with.
        self.batch_packed = batch > 1
        self.microbatch = batch if self.batch_packed else par["microbatch"]
        self.par = par
        self._ex = _Executor(graph, req, backend)
        self._fused = bool(graph.meta.get("fuse_pipeline"))
        self._build()

    # build jitted executables --------------------------------------------
    def _build(self):
        ex = self._ex
        g = self.graph

        def seg_needs(seg):
            names = set(seg["ops"])
            ins, outs = [], []
            for op in g:
                if op.name in names:
                    ins += [i for i in op.inputs if i not in names
                            and i not in ins]
                else:
                    outs += [i for i in op.inputs
                             if i in names and i not in outs]
            # final outputs
            for op in g.outputs():
                if op.name in names and op.name not in outs:
                    outs.append(op.name)
            return ins, outs

        def make_seg_fn(seg, ins, outs):
            ops_ = [g[n] for n in seg["ops"]]
            p_seg = ops_[0].attrs_opt.get("P", 1)

            def body(env_in, feeds):
                env = dict(env_in)
                for op in ops_:
                    vals = [env[i] if i in env else None for i in op.inputs]
                    env[op.name] = ex.run_op(op, vals, feeds)
                return {o: env[o] for o in outs}

            mb = self.microbatch

            def fn(env_in, feeds):
                if self.batch_packed or p_seg >= mb or mb == 1:
                    return body(env_in, feeds)
                nchunk = mb // p_seg

                def split(v):
                    return jax.tree_util.tree_map(
                        lambda a: a.reshape(nchunk, p_seg, *a.shape[1:]), v)

                def join(v):
                    return jax.tree_util.tree_map(
                        lambda a: a.reshape(nchunk * p_seg, *a.shape[2:]), v)

                out = jax.lax.map(lambda ef: body(ef[0], ef[1]),
                                  (split(env_in), split(feeds)))
                return join(out)

            return fn

        plans = []
        for seg in self.segments:
            ins, outs = seg_needs(seg)
            plans.append((seg, ins, outs, make_seg_fn(seg, ins, outs)))
        self._plans = plans

        def whole(feeds):
            env: dict[str, Any] = {}
            for seg, ins, outs, fn in plans:
                env.update(fn({i: env[i] for i in ins if i in env},
                              feeds))
            return env[g.outputs()[0].name]

        self._compose = whole
        if self._fused:
            self._whole = jax.jit(whole)
            self._seg_fns = None
        else:
            self._whole = None
            self._seg_fns = [(seg, ins, outs, jax.jit(fn))
                             for seg, ins, outs, fn in plans]

    def lower(self, feeds):
        """Lower one micro-batch launch as a single program, for
        inspection: ``feeds`` are arrays or ``jax.ShapeDtypeStruct``s
        (possibly placed on a described device), and
        ``.compile().as_text()`` shows the kernels the device runs."""
        return (self._whole or jax.jit(self._compose)).lower(feeds)

    # calibration + weight quantization ------------------------------------
    def calibrate(self, feeds):
        """Run fp over a calibration batch, set activation scales, quantize
        int8 weights (per-output-channel). The recording run uses the
        jnp reference kernels (as ``_calibrate_block`` does), so every
        kernel backend bakes the same scales and its outputs differ
        from the reference only by kernel arithmetic."""
        record: dict[str, float] = {}
        _, env = _Executor(self.graph, self.req, "xla").run(
            feeds, force_fp=True, record=record)
        for op in self.graph:
            if op.name in record:
                op.attrs["act_scale"] = activation_scale(record[op.name])
        for op in self.graph:
            if op.op_type in ("dense", "linear") and op.precision == "int8":
                prod = op.inputs[0]
                op.attrs["in_scale"] = self.graph[prod].attrs.get(
                    "act_scale", 1.0)
                wq, ws = quantize_weight(op.params["w"])
                op.params["w_q"], op.params["w_scale"] = wq, ws
            elif (op.op_type == "gravnet_block"
                  and op.precision == "int8"):
                self._calibrate_block(op, env)
        self._build()  # re-close over updated params/attrs

    def _calibrate_block(self, op, env):
        """Derive the fused int8 block's baked activation scales from
        the fp calibration run. The fused op hides the chain's interior
        tensors from the recording pass, so the two interior scales are
        recomputed here from the block's fp input via the same oracles
        the unfused chain executes: ``in_scale`` is the producer's
        recorded activation scale (quantizes x on kernel entry),
        ``agg_scale`` the fp aggregate's absmax (the aggregate op's
        snap in the unfused chain), and ``h_scale`` the absmax of
        ``concat(x, agg)`` (the concat's scale, which the unfused
        output dense quantizes with). Weights quantize per channel."""
        from repro.kernels import ref as kref
        a, p = op.attrs, op.params
        prod = op.inputs[0]
        a["in_scale"] = self.graph[prod].attrs.get("act_scale", 1.0)
        dh = p["ws"].shape[0]
        x = _as_fp(env[prod])[..., :dh]
        mask = _as_fp(env[op.inputs[1]])
        s = kref.fused_dense_ref(x, p["ws"], p["bs"], activation="none",
                                 out_dtype=jnp.float32)
        f = kref.fused_dense_ref(x, p["wf"], p["bf"], activation="none",
                                 out_dtype=jnp.float32)

        def agg_one(ss, ff, mm):
            return kref.gravnet_aggregate_ref(ss, ff, mm, k=a["k"],
                                              scale=a["scale"],
                                              out_dtype=jnp.float32)

        agg = (jax.vmap(agg_one)(s, f, mask) if x.ndim == 3
               else agg_one(s, f, mask))
        a["agg_scale"] = activation_scale(float(jnp.max(jnp.abs(agg))))
        h = (jnp.concatenate([x, agg], axis=-1)
             if a.get("concat_x", True) else agg)
        a["h_scale"] = activation_scale(float(jnp.max(jnp.abs(h))))
        for nm in ("ws", "wf", "wo"):
            p[nm + "_q"], p[nm + "_scale"] = quantize_weight(p[nm])

    # inference -------------------------------------------------------------
    def __call__(self, feeds):
        b = next(iter(feeds.values())).shape[0]
        mb = self.microbatch
        chunks = []
        pad = (-b) % mb
        if pad:
            feeds = jax.tree_util.tree_map(
                lambda a: jnp.concatenate(
                    [a, jnp.zeros((pad, *a.shape[1:]), a.dtype)]), feeds)
        total = b + pad
        for s in range(0, total, mb):
            chunk = jax.tree_util.tree_map(lambda a: a[s:s + mb], feeds)
            if self._fused:
                chunks.append(self._whole(chunk))
            else:
                env: dict[str, Any] = {}
                for seg, ins, outs, fn in self._seg_fns:
                    env.update(fn({i: env[i] for i in ins if i in env},
                                  chunk))
                chunks.append(env[self.graph.outputs()[0].name])
        out = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *chunks)
        if pad:
            out = jax.tree_util.tree_map(lambda a: a[:b], out)
        return out

    # reporting ---------------------------------------------------------------
    def resource_report(self):
        """Table-I analogue: per-segment FLOPs/bytes/VMEM occupancy."""
        n = self.req.n_hits
        # occupancy against the target chip's VMEM; a CPU has none
        vmem_bytes = (hw.chip_peaks(self.req.device_kind).vmem_bytes
                      if self.req.platform == "tpu" else None)
        rows = []
        for seg in self.segments:
            ops_ = [self.graph[o] for o in seg["ops"]]
            p = ops_[0].attrs_opt.get("P", 1)
            fl = by = wb = 0.0
            for op in ops_:
                f_, a_, w_ = op_cost(op, n)
                fl += f_
                by += a_
                wb += w_
            vmem = wb + p * by
            rows.append({
                "segment": seg["id"], "target": seg["target"], "P": p,
                "ops": len(ops_), "flops_per_event": fl,
                "act_bytes_per_event": by, "weight_bytes": wb,
                "vmem_working_set": vmem,
                "vmem_util": vmem / vmem_bytes if vmem_bytes else None,
                "time_s_per_step": segment_time(ops_, n, p,
                                                self.req.platform,
                                                self.req.device_kind),
            })
        return rows

    def model_throughput(self):
        total = 0.0
        for r in self.resource_report():
            chunks = max(1, self.microbatch // r["P"])
            total += chunks * r["time_s_per_step"]
        return self.microbatch / total if total else float("inf")

    def model_latency(self):
        return sum(r["time_s_per_step"] for r in self.resource_report())


# -------------------------------------------------------------------- deploy ----
def deploy(model_graph: Graph, req: Requirements, *,
           calibration_feeds=None, kernel_backend: str | None = None,
           tuning_cache=None, batch: int = 1,
           fuse_gravnet_block: bool = True,
           fuse_int8: bool = True, ragged: bool = False,
           max_events: int | None = None):
    """Run the design flow and emit one executable.

    ``batch > 1`` emits a *batch-packed* executable: kernels are bound
    (and tuning-cache keys derived) for the shapes one whole
    micro-batch launches, and the compiled object processes ``batch``
    events per launch with no per-segment chunking. ``batch=1`` is the
    legacy per-event-shaped executable.

    ``fuse_gravnet_block`` (default on) collapses every fusable
    dense(S)/dense(F) → gravnet_aggregate [→ concat] → dense(out)
    chain into one ``gravnet_block`` megakernel launch at design
    points ≥ 2. The fp path is bitwise-equal to the unfused chain
    (tested); ``False`` is the escape hatch and reproduces the legacy
    graphs — and their tuning-cache keys — bit-for-bit. Under the
    mixed precision policy the fused blocks run the *quantized*
    megakernel: ``calibrate`` bakes the chain's activation scales into
    the kernel and the block matches the unfused calibrated int8 chain
    within calibration tolerance (tested). ``fuse_int8=False`` is the
    int8-specific escape hatch — mixed deployments keep the legacy
    unfused int8 dense chain and its tuning keys bit-for-bit while fp
    deployments still fuse.

    ``ragged=True`` emits a *padding-free* executable: after fusion
    the graph is raggedized (``passes.ragged``) to consume the
    bin-packed event layout of ``data/ragged.py`` — whole events
    first-fit packed into ``req.n_hits``-row bins, kNN neighbors
    selected on-device by the ``knn_build`` kernel with segment
    masking. ``batch`` then means *bins per launch* (not events), and
    ``max_events`` fixes the static per-launch event capacity of the
    condensation scatter (default ``2 * batch`` — a launch holding
    more events is split, never truncated). The returned
    ``RaggedPipeline`` accepts either a ``data.ragged.RaggedBatch`` or
    the padded ``{hits, mask}`` feeds and reproduces the padded
    pipeline's output structure."""
    import os as _os
    env = _os.environ.get("REPRO_BACKEND")
    if (kernel_backend is None and env and env != "pallas"
            and jax.default_backend() == "tpu"):
        # REPRO_BACKEND is the CPU test lever; on a TPU it would
        # silently swap the compiled kernels for a reference path
        raise ValueError(
            f"REPRO_BACKEND={env!r} on a TPU would replace the compiled "
            "Pallas kernels; unset it, or pass kernel_backend= "
            "explicitly")
    backend = (kernel_backend or env
               or ("pallas" if req.platform == "tpu" else "xla"))
    if ragged and req.precision_policy == "mixed":
        raise NotImplementedError(
            "deploy(ragged=True) does not support the mixed precision "
            "policy yet (no quantized ragged megakernel)")
    from repro.core.passes.verify import verify
    verify(model_graph)  # legality check before any rewrite
    g = model_graph
    if req.design_point >= 2:
        # mixed precision fuses only when calibration data will arrive
        # to bake the quantized megakernel's scales (an uncalibrated
        # mixed deploy raises below anyway)
        block = fuse_gravnet_block and (
            req.precision_policy != "mixed"
            or (fuse_int8 and calibration_feeds is not None))
        g = fuse(g, gravnet_block=block)
        verify(g)        # fusion must preserve well-formedness
    if ragged:
        from repro.core.passes.ragged import raggedize
        g = raggedize(g)
        verify(g)    # the rewrite must preserve well-formedness too
        g.meta["ragged_max_events"] = int(max_events or 2 * batch)
    g = partition(g, tpu_native_gravnet=req.tpu_native_gravnet)
    g = apply_precision_policy(
        g, policy="mixed" if req.precision_policy == "mixed" else "fp")
    g = map_templates(g)
    if req.design_point >= 2:
        g = parallelize(g, req)
    else:
        for op in g:
            op.attrs_opt["P"] = 1
        g.meta["parallelization"] = {"P_mxu": 1, "P_xla": 1, "microbatch": 1,
                                     "model_throughput_ev_s": None,
                                     "target": req.target_throughput}
    if req.design_point >= 3:
        g = kernel_optimize(g, n_rows=req.n_hits, batch=batch,
                            tuning_cache=tuning_cache, backend=backend)
    pipe = CompiledPipeline(g, req, backend, batch=batch)
    if req.precision_policy == "mixed":
        if calibration_feeds is None:
            raise ValueError("mixed precision requires calibration_feeds")
        pipe.calibrate(calibration_feeds)
    if ragged:
        return RaggedPipeline(pipe, batch=batch,
                              max_events=g.meta["ragged_max_events"],
                              capacity=req.n_hits,
                              example_feeds=calibration_feeds)
    return pipe


# ----------------------------------------------------- bucketed deployment ----
def _cut_hits(feeds: dict, n: int) -> dict:
    """Slice (or zero-pad) every feed's hit axis (axis 1) to exactly
    ``n`` rows. Events are energy-sorted upstream (data/belle2), so an
    overflow slice keeps the hardest hits. Already-cut feeds (the
    serving dispatch path — ``submit`` cuts per event) pass through
    untouched, so the hot path pays no copy."""
    out = {}
    for key, v in feeds.items():
        if v.shape[1] == n:
            out[key] = v
        elif v.shape[1] > n:
            out[key] = v[:, :n]
        else:
            pw = [(0, 0)] * v.ndim
            pw[1] = (0, n - v.shape[1])
            out[key] = jnp.pad(jnp.asarray(v), pw)
    return out


class BucketedPipeline:
    """Occupancy-bucketed, batch-packed deployment.

    One ``CompiledPipeline`` per (bucket, microbatch) pair: events are
    classified by non-zero hit count and run through the smallest
    bucket executable that fits them (overflow → largest bucket), so
    low-occupancy events stop paying the full-detector launch.
    ``__call__`` reproduces the single-pipeline API — it classifies a
    feed batch, packs each bucket's events into ``microbatch``-wide
    launches, and reassembles results in submission order (per-hit
    output heads are zero-padded up to the widest bucket used so the
    batch stacks). Serving integrates through ``infer_fns()`` +
    ``classify()`` (see ``serving.ShardedTriggerService(buckets=…)``).
    """

    def __init__(self, pipes: dict[int, CompiledPipeline], *,
                 microbatch: int, mask_feed: str = "mask",
                 example_feeds: dict | None = None):
        if not pipes:
            raise ValueError("BucketedPipeline: no bucket executables")
        self.pipes = {b: pipes[b] for b in sorted(pipes)}
        self.buckets = tuple(sorted(pipes))
        self.backend = self.pipes[self.buckets[0]].backend
        self.microbatch = microbatch
        self.mask_feed = mask_feed
        # example feeds (calibration slice) drive warmup compilation
        self._example = example_feeds

    # ------------------------------------------------------- classification --
    def classify(self, occupancy: int) -> int:
        from repro.serving.router import pick_bucket_sorted
        return pick_bucket_sorted(occupancy, self.buckets)

    def _occupancies(self, feeds):
        import numpy as np
        return np.count_nonzero(
            np.asarray(feeds[self.mask_feed]) > 0, axis=1)

    # --------------------------------------------------------------- infer --
    def __call__(self, feeds):
        import numpy as np
        occ = self._occupancies(feeds)
        b_total = occ.shape[0]
        groups: dict[int, list[int]] = {}
        for i, o in enumerate(occ):
            groups.setdefault(self.classify(int(o)), []).append(i)
        per_bucket = []
        for bucket, idxs in sorted(groups.items()):
            sub = jax.tree_util.tree_map(
                lambda a: jnp.asarray(a)[jnp.asarray(idxs)], feeds)
            out = self.pipes[bucket](_cut_hits(sub, bucket))
            per_bucket.append((idxs, out))
        # reassemble in submission order; pad differing per-hit axes
        # (axis 1) up to the widest bucket used in this call
        leaves0, tdef = jax.tree_util.tree_flatten(per_bucket[0][1])
        flat = [(idxs, jax.tree_util.tree_flatten(out)[0])
                for idxs, out in per_bucket]
        result_leaves = []
        for li in range(len(leaves0)):
            parts = [(idxs, np.asarray(ls[li])) for idxs, ls in flat]
            widest = max(p.shape[1] if p.ndim >= 2 else 0
                         for _, p in parts)
            buf = None
            for idxs, p in parts:
                if p.ndim >= 2 and p.shape[1] < widest:
                    pw = [(0, 0)] * p.ndim
                    pw[1] = (0, widest - p.shape[1])
                    p = np.pad(p, pw)
                if buf is None:
                    buf = np.zeros((b_total, *p.shape[1:]), p.dtype)
                buf[np.asarray(idxs)] = p
            result_leaves.append(buf)
        return jax.tree_util.tree_unflatten(tdef, result_leaves)

    # ------------------------------------------------------------- serving --
    def infer_fns(self) -> dict:
        """{bucket: infer_fn} for the serving layer; each fn expects
        feeds already cut to its bucket's hit count (the service slices
        on submit) and runs one batch-packed launch."""
        return {b: (lambda feeds, _p=self.pipes[b], _b=b:
                    _p(_cut_hits(feeds, _b)))
                for b in self.buckets}

    def warmup_one(self, bucket: int) -> int:
        """Pre-compile one bucket's (bucket, microbatch) executable;
        returns 1 when warmed (0 with no example feeds). The serving
        layer calls this once per (device, bucket) so a bucket's
        replicas never pay for their siblings' shapes."""
        if self._example is None:
            return 0
        ex = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a)[:self.microbatch], self._example)
        # CompiledPipeline.__call__ pads any batch up to the microbatch
        # multiple, so a short example still compiles the served shape
        jax.block_until_ready(jax.tree_util.tree_leaves(
            self.pipes[bucket](_cut_hits(ex, bucket))))
        return 1

    def warmup(self) -> int:
        """Pre-compile every (bucket, microbatch) executable so the
        first real event of any occupancy never pays jit tracing.
        Returns the number of bucket executables warmed."""
        return sum(self.warmup_one(b) for b in self.buckets)

    # ----------------------------------------------------------- reporting --
    def resource_report(self):
        return {b: p.resource_report() for b, p in self.pipes.items()}


def deploy_bucketed(model_graph: Graph, req: Requirements, *,
                    buckets=(32, 64, 128), microbatch: int = 8,
                    calibration_feeds=None,
                    kernel_backend: str | None = None,
                    tuning_cache=None,
                    fuse_gravnet_block: bool = True,
                    fuse_int8: bool = True) -> BucketedPipeline:
    """Run the design flow once per occupancy bucket.

    Each bucket b gets its own batch-packed executable deployed at
    ``n_hits=b`` (kernel bindings, tuning keys, and precision
    calibration all see the bucket's true shape). ``calibration_feeds``
    are sliced to each bucket's hit count, so int8 activation scales
    are calibrated on the occupancy tier they will serve."""
    import dataclasses as _dc
    bs = sorted(set(int(b) for b in buckets))
    if not bs or bs[0] <= 0:
        raise ValueError(f"invalid buckets {buckets!r}")
    pipes = {}
    for b in bs:
        req_b = _dc.replace(req, n_hits=b)
        calib_b = None if calibration_feeds is None \
            else _cut_hits(calibration_feeds, b)
        pipes[b] = deploy(model_graph, req_b, calibration_feeds=calib_b,
                          kernel_backend=kernel_backend,
                          tuning_cache=tuning_cache, batch=microbatch,
                          fuse_gravnet_block=fuse_gravnet_block,
                          fuse_int8=fuse_int8)
    return BucketedPipeline(pipes, microbatch=microbatch,
                            example_feeds=calibration_feeds)


# ------------------------------------------------------- ragged deployment ----
class RaggedPipeline:
    """Padding-free bin-packed deployment (see ``deploy(ragged=True)``).

    Wraps one raggedized ``CompiledPipeline`` whose launch shape is a
    fixed number of ``capacity``-row bins. ``__call__`` accepts either
    a ``data.ragged.RaggedBatch`` (concatenated hits + CSR offsets) or
    the padded ``{hits, mask}`` feeds; events are first-fit packed
    whole into bins, launches are capped at the executable's bin count
    *and* at ``max_events`` events (the condensation scatter's static
    event capacity — overflow splits launches, never truncates an
    event), and per-event results are scattered back so the output
    matches the dense pipeline's structure:
    ``{head: (n_events, capacity, d), 'cps': {…: (n_events, …)}}``.
    """

    def __init__(self, pipe: CompiledPipeline, *, batch: int,
                 max_events: int, capacity: int,
                 example_feeds: dict | None = None):
        if not pipe.graph.meta.get("ragged"):
            raise ValueError("RaggedPipeline needs a raggedized graph "
                             "(deploy(ragged=True) builds one)")
        self.pipe = pipe
        self.backend = pipe.backend
        # bins per launch = the executable's microbatch, so every call
        # is exactly one chunk (no zero-padding: an all-zero pad bin
        # would alias segment id 0)
        self.batch = int(pipe.microbatch)
        self.max_events = int(max_events)
        self.capacity = int(capacity)
        self._example = example_feeds

    # ------------------------------------------------------------ planning --
    def _plan_launches(self, counts) -> list[tuple[int, int]]:
        """Split the event stream into contiguous ``[i, j)`` launch
        ranges by simulating the same first-fit packing ``bin_pack``
        performs, closing a launch when the next event would need a
        ``batch+1``-th bin or exceed ``max_events``."""
        launches = []
        start, n_ev, free = 0, 0, []
        for e, c in enumerate(counts):
            c = int(c)
            if c > self.capacity:
                raise ValueError(
                    f"event {e} has {c} hits > bin capacity "
                    f"{self.capacity} — it cannot be packed")
            placed = False
            for i, f in enumerate(free):
                if c <= f:
                    free[i] -= c
                    placed = True
                    break
            needs_bin = not placed
            if (needs_bin and len(free) == self.batch) \
                    or n_ev == self.max_events:
                launches.append((start, e))
                start, n_ev, free = e, 0, []
                needs_bin = True
            if needs_bin:
                free.append(self.capacity - c)
            n_ev += 1
        if n_ev or not launches:
            launches.append((start, start + n_ev))
        return launches

    # --------------------------------------------------------------- infer --
    def __call__(self, feeds):
        import numpy as np

        from repro.data.ragged import (RaggedBatch, bin_pack, pack_events,
                                       unpack_binned)
        if isinstance(feeds, RaggedBatch):
            rb = feeds
        else:
            rb = pack_events(np.asarray(feeds["hits"]),
                             np.asarray(feeds["mask"]))
        counts = rb.counts()
        offs = np.asarray(rb.offsets)
        parts = []
        for i, j in self._plan_launches(counts):
            sub = RaggedBatch(feats=rb.feats[offs[i]:offs[j]],
                              offsets=offs[i:j + 1] - offs[i])
            bp = bin_pack(sub, self.capacity, n_bins=self.batch)
            mask = (np.asarray(bp.segids) >= 0).astype(np.float32)
            out = self.pipe({"hits": jnp.asarray(bp.feats),
                             "mask": jnp.asarray(mask),
                             "segids": jnp.asarray(bp.segids),
                             "slots": jnp.asarray(bp.slots)})
            n_ev = j - i
            part = {}
            for name, v in out.items():
                if name == "cps":
                    part[name] = {k: np.asarray(a)[:n_ev]
                                  for k, a in v.items()}
                else:
                    part[name] = unpack_binned(
                        np.asarray(v), np.asarray(bp.segids),
                        np.asarray(bp.slots), n_ev, self.capacity)
            parts.append(part)
        if len(parts) == 1:
            return parts[0]
        return jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=0), *parts)

    # -------------------------------------------------------------- warmup --
    def warmup(self) -> int:
        """Pre-compile the (batch × capacity)-bin executable so the
        first real submission never pays jit tracing. Uses the example
        feeds when given, else a synthetic full-occupancy batch."""
        import numpy as np
        if self._example is not None:
            feeds = {k: np.asarray(v) for k, v in self._example.items()
                     if k in ("hits", "mask")}
        else:
            rng = np.random.default_rng(0)
            d = self.pipe.graph["hits"].out_dim
            feeds = {"hits": rng.normal(size=(self.batch, self.capacity,
                                              d)).astype(np.float32),
                     "mask": np.ones((self.batch, self.capacity),
                                     np.float32)}
        jax.block_until_ready(jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(jnp.asarray, self(feeds))))
        return 1

    # ----------------------------------------------------------- reporting --
    def resource_report(self):
        return self.pipe.resource_report()
