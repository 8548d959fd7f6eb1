"""Operator registry — the declarations the deploy passes dispatch on.

Every graph-IR op type is described once, here, by an :class:`OpSpec`:
whether its access pattern is regular (MXU-eligible), which
architecture template it lowers onto per target, how to infer its
output feature dim (verification), its analytic cost model
(parallelization), and how it binds kernel launch knobs / tuning-cache
keys (kernel-level optimization). The passes in ``core/passes`` are
pattern-keyed interpreters over these declarations: none of them knows
any model by name, and opening the flow to a new op family (e.g. the
edge-based message-passing GNNs) means registering specs — not editing
five pass bodies.

Fusion is the same story at the subgraph level: rewrites such as the
GravNet-block collapse register as :class:`FusionRule` entries
(``core/passes/fusion.py``) and ``fuse()`` replays them in
registration order.

Registered op families:

- classic dataflow: ``input``/``output``, ``linear``/``dense``,
  ``relu``, ``concat``, ``slice``, ``retile``, ``quant``/``dequant``
- CaloClusterNet irregulars: ``gravnet_aggregate``, ``gravnet_block``
  (the fused megakernel), ``cps``
- attention: ``attention`` (flash kernel)
- edge-based message passing: ``gather_edge`` (endpoint gather by an
  explicit edge list), ``edge_aggregate`` (masked segment-sum/mean of
  per-edge messages into nodes), ``eltwise`` (n-ary elementwise
  algebra), ``batchnorm`` (inference-mode batch normalization from
  running statistics, padded rows masked)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable


class GraphVerificationError(ValueError):
    """A graph failed shape/legality checks (see passes/verify.py)."""


class UnknownOperatorError(GraphVerificationError):
    """An op type absent from the registry — no pass can handle it."""


@dataclasses.dataclass(frozen=True)
class BindContext:
    """What the kernel-opt pass knows when binding launch knobs."""
    n_rows: int
    batch: int = 1
    cache: Any = None        # repro.tuning.cache.TuningCache | None
    backend: str = "xla"


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """Declarative description of one op type, consumed by the passes.

    ``infer(op, dims, g)``     -> output feature dim (verify pass)
    ``cost(op, n_hits, pb)``   -> (flops, act_bytes, weight_bytes)
    ``mxu_eff(op, rows, n)``   -> fraction of MXU peak (matmuls only)
    ``bind(op, ctx)``          -> write launch knobs into op.attrs_opt
    ``tuning_key(op, n, be, b)``-> KernelKey | None (autotuner problems)
    """
    op_type: str
    regular: bool = False            # statically scheduled -> MXU-eligible
    tpu_native_regular: bool = False  # regular under tpu_native_gravnet
    templates: dict[str, str] = dataclasses.field(default_factory=dict)
    infer: Callable | None = None
    cost: Callable | None = None
    mxu_matmul: bool = False         # cost model treats it as a matmul
    mxu_eff: Callable | None = None
    bind: Callable | None = None
    tuning_key: Callable | None = None
    int8_passthrough: bool = False   # int8 chain fusion may emit through it


@dataclasses.dataclass(frozen=True)
class FusionRule:
    """One registered subgraph rewrite, replayed by ``fuse()`` in
    registration order. ``opt_in`` rules run only when the caller
    enables them by name; ``fixpoint`` rules iterate until the graph
    stops shrinking."""
    name: str
    fn: Callable  # Graph -> Graph
    opt_in: bool = False
    fixpoint: bool = False


_REGISTRY: dict[str, OpSpec] = {}
_FUSION_RULES: list[FusionRule] = []


def register_op(spec: OpSpec) -> OpSpec:
    if spec.op_type in _REGISTRY:
        raise ValueError(f"op type {spec.op_type!r} already registered")
    _REGISTRY[spec.op_type] = spec
    return spec


def op_spec(op_type: str) -> OpSpec | None:
    return _REGISTRY.get(op_type)


def require_spec(op) -> OpSpec:
    """Spec for ``op`` (an Operator), or the canonical unknown-op error."""
    spec = _REGISTRY.get(op.op_type)
    if spec is None:
        raise UnknownOperatorError(
            f"{op.name}: unknown op {op.op_type!r}")
    return spec


def registered_ops() -> frozenset[str]:
    return frozenset(_REGISTRY)


def regular_ops() -> frozenset[str]:
    return frozenset(t for t, s in _REGISTRY.items() if s.regular)


def irregular_ops() -> frozenset[str]:
    return frozenset(t for t, s in _REGISTRY.items() if not s.regular)


def is_regular(op, *, tpu_native_gravnet: bool = False) -> bool:
    spec = require_spec(op)
    return spec.regular or (tpu_native_gravnet and spec.tpu_native_regular)


def unknown_ops(g) -> list[tuple[str, str]]:
    """(node name, op type) for every op the registry does not know."""
    return [(op.name, op.op_type) for op in g
            if op.op_type not in _REGISTRY]


def register_fusion_rule(name: str, fn: Callable, *, opt_in: bool = False,
                         fixpoint: bool = False) -> FusionRule:
    if any(r.name == name for r in _FUSION_RULES):
        raise ValueError(f"fusion rule {name!r} already registered")
    rule = FusionRule(name, fn, opt_in=opt_in, fixpoint=fixpoint)
    _FUSION_RULES.append(rule)
    return rule


def fusion_rules() -> tuple[FusionRule, ...]:
    return tuple(_FUSION_RULES)


# ------------------------------------------------------------------------
# template layouts: what each template produces / expects on data edges.
# MXU templates exchange ``lane128`` tensors (feature dim zero-padded to
# the VREG lane width); everything else exchanges ``compact`` tensors.
# The fused gravnet_block hands tensors over in lane128 on BOTH targets
# (its executor slices/pads its own operands) — see passes/mapping.py.
LANE = 128
TEMPLATE_LAYOUT = {"fused_dense": "lane128", "gravnet_kernel": "lane128",
                   "gravnet_block_kernel": "lane128",
                   "xla_gravnet_block": "lane128"}


def template_layout(template: str | None) -> str:
    return TEMPLATE_LAYOUT.get(template, "compact")


# ========================================================================
# shape inference (verify pass arms)
# ========================================================================
def _infer_input(op, dims, g):
    if op.out_dim is None:
        raise GraphVerificationError(f"{op.name}: input needs out_dim")
    return op.out_dim


def _infer_dense(op, dims, g):
    if not op.params or "w" not in op.params:
        raise GraphVerificationError(f"{op.name}: missing weight")
    d_in, d_out = op.params["w"].shape
    got = dims[op.inputs[0]]
    if got != d_in:
        raise GraphVerificationError(
            f"{op.name}: weight expects d_in={d_in}, producer "
            f"{op.inputs[0]!r} provides {got}")
    if "b" in op.params and op.params["b"].shape != (d_out,):
        raise GraphVerificationError(f"{op.name}: bias shape "
                                     f"{op.params['b'].shape}")
    return d_out


def _infer_same(op, dims, g):
    return dims[op.inputs[0]]


def _infer_retile(op, dims, g):
    return op.out_dim or dims[op.inputs[0]]


def _infer_concat(op, dims, g):
    return sum(dims[i] for i in op.inputs)


def _infer_slice(op, dims, g):
    st, sz = op.attrs["start"], op.attrs["size"]
    if st + sz > dims[op.inputs[0]]:
        raise GraphVerificationError(
            f"{op.name}: slice [{st}:{st + sz}] exceeds producer "
            f"dim {dims[op.inputs[0]]}")
    return sz


def _infer_gravnet_aggregate(op, dims, g):
    ins = op.inputs
    if len(ins) != 3:
        raise GraphVerificationError(
            f"{op.name}: needs (s, f, mask) inputs")
    ds, df = op.attrs.get("d_s"), op.attrs.get("d_f")
    if dims[ins[0]] != ds or dims[ins[1]] != df:
        raise GraphVerificationError(
            f"{op.name}: S/FLR dims ({dims[ins[0]]},{dims[ins[1]]})"
            f" != attrs ({ds},{df})")
    return 2 * df


def _infer_gravnet_block(op, dims, g):
    ins = op.inputs
    if len(ins) != 2:
        raise GraphVerificationError(
            f"{op.name}: needs (x, mask) inputs")
    need = ("ws", "bs", "wf", "bf", "wo", "bo")
    if not op.params or any(p not in op.params for p in need):
        raise GraphVerificationError(
            f"{op.name}: gravnet_block needs params {need}")
    dh = op.attrs.get("d_hidden")
    ds, df = op.attrs.get("d_s"), op.attrs.get("d_f")
    if dims[ins[0]] != dh:
        raise GraphVerificationError(
            f"{op.name}: x provides {dims[ins[0]]}, expects "
            f"d_hidden={dh}")
    if op.params["ws"].shape != (dh, ds):
        raise GraphVerificationError(
            f"{op.name}: ws shape {op.params['ws'].shape} != "
            f"({dh},{ds})")
    if op.params["wf"].shape != (dh, df):
        raise GraphVerificationError(
            f"{op.name}: wf shape {op.params['wf'].shape} != "
            f"({dh},{df})")
    dcat = (dh + 2 * df if op.attrs.get("concat_x", True)
            else 2 * df)
    if op.params["wo"].shape[0] != dcat:
        raise GraphVerificationError(
            f"{op.name}: wo expects {op.params['wo'].shape[0]} "
            f"inputs, block provides {dcat}")
    return int(op.params["wo"].shape[1])


def _infer_attention(op, dims, g):
    ins = op.inputs
    if len(ins) != 3:
        raise GraphVerificationError(
            f"{op.name}: needs (q, k, v) inputs")
    if len({dims[i] for i in ins}) != 1:
        raise GraphVerificationError(
            f"{op.name}: q/k/v dims differ: "
            f"{[dims[i] for i in ins]}")
    return dims[ins[0]]


def _infer_cps(op, dims, g):
    heads = op.attrs.get("head_names", [])
    # ragged form (passes/ragged.py) consumes (heads..., segids, slots)
    aux = 2 if op.attrs.get("ragged") else 1
    if len(op.inputs) != len(heads) + aux:
        raise GraphVerificationError(
            f"{op.name}: expects {len(heads)} heads + "
            f"{'segids/slots' if aux == 2 else 'mask'}, got "
            f"{len(op.inputs)} inputs")
    return op.out_dim or 1


def _infer_output(op, dims, g):
    return sum(dims[i] for i in op.inputs
               if g[i].op_type != "cps")


def _infer_knn_build(op, dims, g):
    if len(op.inputs) != 2:
        raise GraphVerificationError(
            f"{op.name}: needs (s, segids) inputs")
    ds = op.attrs.get("d_s")
    if dims[op.inputs[0]] != ds:
        raise GraphVerificationError(
            f"{op.name}: S dim {dims[op.inputs[0]]} != attrs d_s={ds}")
    return op.attrs["k"]


def _infer_knn_aggregate(op, dims, g):
    if len(op.inputs) != 2:
        raise GraphVerificationError(
            f"{op.name}: needs (f, knn) inputs")
    df = op.attrs.get("d_f")
    if dims[op.inputs[0]] != df:
        raise GraphVerificationError(
            f"{op.name}: FLR dim {dims[op.inputs[0]]} != attrs "
            f"d_f={df}")
    if g[op.inputs[1]].op_type != "knn_build":
        raise GraphVerificationError(
            f"{op.name}: neighbor input {op.inputs[1]!r} must be a "
            "knn_build op")
    return 2 * df


def _infer_gather_edge(op, dims, g):
    if len(op.inputs) != 2:
        raise GraphVerificationError(
            f"{op.name}: needs (nodes, edge_index) inputs")
    if op.attrs.get("endpoint") not in ("src", "dst"):
        raise GraphVerificationError(
            f"{op.name}: endpoint must be 'src' or 'dst', got "
            f"{op.attrs.get('endpoint')!r}")
    return dims[op.inputs[0]]


def _infer_edge_aggregate(op, dims, g):
    if len(op.inputs) not in (2, 3):
        raise GraphVerificationError(
            f"{op.name}: needs (messages, edge_index[, edge_mask]) "
            "inputs")
    if op.attrs.get("reduce", "sum") not in ("sum", "mean"):
        raise GraphVerificationError(
            f"{op.name}: reduce must be 'sum' or 'mean', got "
            f"{op.attrs.get('reduce')!r}")
    return dims[op.inputs[0]]


_ELTWISE_FNS = ("add", "mul", "div", "sigmoid", "relu", "mask",
                "add_const", "l2norm")


def _infer_eltwise(op, dims, g):
    fn = op.attrs.get("fn")
    if fn not in _ELTWISE_FNS:
        raise GraphVerificationError(
            f"{op.name}: eltwise fn must be one of {_ELTWISE_FNS}, "
            f"got {fn!r}")
    if fn in ("add", "mul", "div"):
        if len({dims[i] for i in op.inputs}) != 1:
            raise GraphVerificationError(
                f"{op.name}: eltwise {fn} operand dims differ: "
                f"{[dims[i] for i in op.inputs]}")
    if fn == "mask" and len(op.inputs) != 2:
        raise GraphVerificationError(
            f"{op.name}: eltwise mask needs (x, mask) inputs")
    return dims[op.inputs[0]]


_BN_PARAMS = ("gamma", "beta", "mean", "var")


def _infer_batchnorm(op, dims, g):
    if len(op.inputs) != 2:
        raise GraphVerificationError(
            f"{op.name}: needs (x, mask) inputs")
    d = dims[op.inputs[0]]
    for k in _BN_PARAMS:
        v = (op.params or {}).get(k)
        if v is None or tuple(v.shape) != (d,):
            raise GraphVerificationError(
                f"{op.name}: batchnorm needs a ({d},) {k!r} param, got "
                f"{None if v is None else tuple(v.shape)}")
    if "eps" not in op.attrs:
        raise GraphVerificationError(f"{op.name}: batchnorm needs an eps")
    return d


# ========================================================================
# analytic cost model (parallelize pass arms): (flops, act, wb) / event
# ========================================================================
def _cost_dense(op, n_hits, pb):
    d_out = op.out_dim or 1
    d_in = op.params["w"].shape[0] if op.params else d_out
    flops = 2.0 * n_hits * d_in * d_out
    act = n_hits * (d_in + d_out) * pb
    wb = d_in * d_out * pb
    return flops, act, wb


def _cost_gravnet_aggregate(op, n_hits, pb):
    d_out = op.out_dim or 1
    ds = op.attrs.get("d_s", 4)
    df = op.attrs.get("d_f", d_out // 2)
    k = op.attrs.get("k", 8)
    flops = 2.0 * n_hits * n_hits * (ds + k * df) + 10.0 * n_hits * k
    act = n_hits * (ds + df + d_out) * pb
    return flops, act, 0.0


def _cost_gravnet_block(op, n_hits, pb):
    # fused dense(S)∥dense(F) → aggregate → dense(out): compute is
    # the sum of the parts, but only x and the block output touch
    # HBM — the S/F/aggregate intermediates stay in VMEM (the point
    # of the megakernel)
    d_out = op.out_dim or 1
    dh = op.attrs.get("d_hidden", 64)
    ds = op.attrs.get("d_s", 4)
    df = op.attrs.get("d_f", d_out // 2)
    k = op.attrs.get("k", 8)
    dcat = dh + 2 * df if op.attrs.get("concat_x", True) else 2 * df
    flops = (2.0 * n_hits * dh * (ds + df)              # prologue
             + 2.0 * n_hits * n_hits * (ds + k * df)    # aggregate
             + 10.0 * n_hits * k
             + 2.0 * n_hits * dcat * d_out)             # epilogue
    act = n_hits * (dh + d_out) * pb
    wb = (dh * (ds + df) + dcat * d_out) * pb
    return flops, act, wb


def _cost_attention(op, n_hits, pb):
    d = op.out_dim or 1
    flops = 4.0 * n_hits * n_hits * d + 10.0 * n_hits * n_hits
    act = n_hits * 4.0 * d * pb
    return flops, act, 0.0


def _cost_cps(op, n_hits, pb):
    kmax = op.attrs.get("k_max", 8)
    flops = 20.0 * n_hits * kmax + 10.0 * n_hits * math.log2(max(n_hits, 2))
    act = n_hits * 8.0 * pb
    return flops, act, 0.0


def _cost_knn_build(op, n_hits, pb):
    # gravnet_aggregate's selection half: the (n, n) distance matmul
    # plus k argmin/knockout sweeps
    ds = op.attrs.get("d_s", 4)
    k = op.attrs.get("k", 8)
    flops = 2.0 * n_hits * n_hits * ds + 10.0 * n_hits * k
    act = n_hits * (ds + 2.0 * k) * pb
    return flops, act, 0.0


def _cost_knn_aggregate(op, n_hits, pb):
    # gravnet_aggregate's aggregation half: k one-hot (n, n) @ (n, df)
    # selection matmuls plus the weighting sweeps
    d_out = op.out_dim or 1
    df = op.attrs.get("d_f", d_out // 2)
    k = op.attrs.get("k", 8)
    flops = 2.0 * n_hits * n_hits * k * df + 10.0 * n_hits * k
    act = n_hits * (df + d_out + 2.0 * k) * pb
    return flops, act, 0.0


def _cost_eltwise_like(op, n_hits, pb):
    d_out = op.out_dim or 1
    flops = 1.0 * n_hits * d_out
    act = 2.0 * n_hits * d_out * pb
    return flops, act, 0.0


def _n_edges(op, n_hits):
    # edge slots per event: exporters record the in-edges per node
    # (E = in_edges · n_hits) or the padded edge count; fall back to a
    # sparse power-law-ish estimate when absent
    if op.attrs.get("in_edges"):
        return int(op.attrs["in_edges"]) * n_hits
    return int(op.attrs.get("n_edges") or 4 * n_hits)


def _cost_gather_edge(op, n_hits, pb):
    d_out = op.out_dim or 1
    e = _n_edges(op, n_hits)
    flops = 1.0 * e * d_out
    act = (n_hits * d_out + e * (d_out + 2.0)) * pb
    return flops, act, 0.0


def _cost_edge_aggregate(op, n_hits, pb):
    # the lowered kernel (kernels/edge_aggregate.py): a (n, E) one-hot
    # incidence slab built by compare and mask, times the (E, d)
    # messages — n·E·d multiply-adds, not the segment sum's E·d adds
    d_out = op.out_dim or 1
    e = _n_edges(op, n_hits)
    flops = 2.0 * n_hits * e * d_out + 2.0 * n_hits * e
    act = (e * d_out + 2.0 * e + n_hits * d_out) * pb
    return flops, act, 0.0


def _cost_batchnorm(op, n_hits, pb):
    # inference mode: a per-channel affine and the row mask
    d_out = op.out_dim or 1
    flops = 3.0 * n_hits * d_out
    act = 2.0 * n_hits * d_out * pb
    wb = 4.0 * d_out * pb           # gamma, beta, mean, var
    return flops, act, wb


def default_cost(op, n_hits, pb):
    return 0.0, n_hits * (op.out_dim or 1) * pb, 0.0


# MXU-efficiency factors (fraction of systolic-array peak a matmul of
# this size can use; consulted only for mxu-targeted matmul ops)
def _eff_dense(op, n_rows, n_hits):
    d_in = op.params["w"].shape[0] if op.params else 128
    d_out = op.out_dim or 128
    return (min(d_in, 128) / 128.0) * (min(d_out, 128) / 128.0) * \
        min(1.0, n_rows / 8.0)


def _eff_gravnet(op, n_rows, n_hits):
    # one-hot selection matmuls: (rows, n_hits) @ (n_hits, d_f)
    df = op.attrs.get("d_f", 32)
    return (min(n_hits, 128) / 128.0) * (min(df, 128) / 128.0)


def _eff_attention(op, n_rows, n_hits):
    d = op.out_dim or 128
    return (min(n_hits, 128) / 128.0) * (min(d, 128) / 128.0)


# ========================================================================
# kernel-opt binders + tuning-cache problem keys
# ========================================================================
def _bind_fused_dense(op, ctx: BindContext):
    """Variant selection / block tuning for the fused_dense template
    (cached winner > heuristic) — see passes/kernel_opt.py."""
    from repro.core.passes.kernel_opt import (_FUSED_DENSE_KNOBS,
                                              fused_dense_default,
                                              fused_dense_dtype,
                                              fused_dense_shape)
    if op.template != "fused_dense":
        return
    rows, d_in, d_out = fused_dense_shape(op, ctx.n_rows, ctx.batch)
    tuned = None
    if ctx.cache is not None:
        from repro.tuning.cache import fused_dense_key
        tuned = ctx.cache.lookup(fused_dense_key(
            rows, d_in, d_out, fused_dense_dtype(op), ctx.backend))
    if tuned is not None:
        for knob in _FUSED_DENSE_KNOBS:
            if knob in tuned:
                op.attrs_opt[knob] = tuned[knob]
        # provenance: the executor only overrides its built-in int8
        # block defaults for configs that were actually searched
        op.attrs_opt["tuned"] = True
    else:
        op.attrs_opt.update(fused_dense_default(rows, d_in, d_out))


def _bind_gravnet_aggregate(op, ctx: BindContext):
    # cache-only (the kernel's own default is the heuristic; a miss
    # leaves attrs_opt untouched → identical bindings)
    if ctx.cache is None:
        return
    from repro.tuning.cache import gravnet_key
    tuned = ctx.cache.lookup(gravnet_key(
        ctx.n_rows, op.attrs["d_s"], op.attrs["d_f"], op.attrs["k"],
        "float32", ctx.backend, batch=ctx.batch))
    if tuned is not None and "bm" in tuned:
        op.attrs_opt["bm"] = tuned["bm"]


def _bind_gravnet_block(op, ctx: BindContext):
    # cache-only (bm, bn, bk) bindings; a miss keeps the wrapper's
    # bitwise-safe defaults. An int8 block keys with the dtype-tagged
    # gravnet_block_int8 family — the quantized megakernel's winners
    # never bind onto the f32 kernel or vice versa.
    if ctx.cache is None:
        return
    from repro.tuning.cache import (gravnet_block_int8_key,
                                    gravnet_block_key)
    if op.precision == "int8":
        key = gravnet_block_int8_key(
            ctx.n_rows, op.attrs["d_hidden"], op.attrs["d_f"],
            op.attrs["k"], ctx.backend, batch=ctx.batch)
    else:
        key = gravnet_block_key(
            ctx.n_rows, op.attrs["d_hidden"], op.attrs["d_f"],
            op.attrs["k"], "float32", ctx.backend, batch=ctx.batch)
    tuned = ctx.cache.lookup(key)
    if tuned is not None:
        for knob in ("bm", "bn", "bk"):
            if knob in tuned:
                op.attrs_opt[knob] = tuned[knob]


def _bind_attention(op, ctx: BindContext):
    if ctx.cache is None:
        return
    from repro.tuning.cache import flash_attention_key
    tuned = ctx.cache.lookup(flash_attention_key(
        ctx.batch, ctx.n_rows, ctx.n_rows, op.out_dim or 128, "float32",
        ctx.backend))
    if tuned is not None:
        for knob in ("bq", "bk"):
            if knob in tuned:
                op.attrs_opt[knob] = tuned[knob]


def _bind_edge_aggregate(op, ctx: BindContext):
    if ctx.cache is None:
        return
    from repro.tuning.cache import edge_aggregate_key
    tuned = ctx.cache.lookup(edge_aggregate_key(
        ctx.n_rows, _n_edges(op, ctx.n_rows), op.out_dim or 1,
        "float32", ctx.backend, batch=ctx.batch))
    if tuned is not None:
        for knob in ("bm", "be"):
            if knob in tuned:
                op.attrs_opt[knob] = tuned[knob]


def _bind_knn_build(op, ctx: BindContext):
    # cache-only bm binding (the wrapper's own default is the
    # heuristic; a miss leaves attrs_opt untouched)
    if ctx.cache is None:
        return
    from repro.tuning.cache import knn_build_key
    tuned = ctx.cache.lookup(knn_build_key(
        ctx.n_rows, op.attrs["d_s"], op.attrs["k"], "float32",
        ctx.backend, batch=ctx.batch))
    if tuned is not None and "bm" in tuned:
        op.attrs_opt["bm"] = tuned["bm"]


def _bind_knn_aggregate(op, ctx: BindContext):
    if ctx.cache is None:
        return
    from repro.tuning.cache import knn_aggregate_key
    tuned = ctx.cache.lookup(knn_aggregate_key(
        ctx.n_rows, op.attrs["d_f"], op.attrs["k"], "float32",
        ctx.backend, batch=ctx.batch))
    if tuned is not None and "bm" in tuned:
        op.attrs_opt["bm"] = tuned["bm"]


def _key_fused_dense(op, n_rows, backend, batch):
    from repro.core.passes.kernel_opt import (fused_dense_dtype,
                                              fused_dense_shape)
    from repro.tuning.cache import fused_dense_key
    rows, d_in, d_out = fused_dense_shape(op, n_rows, batch)
    return fused_dense_key(rows, d_in, d_out, fused_dense_dtype(op),
                           backend)


def _key_gravnet_aggregate(op, n_rows, backend, batch):
    from repro.tuning.cache import gravnet_key
    return gravnet_key(n_rows, op.attrs["d_s"], op.attrs["d_f"],
                       op.attrs["k"], "float32", backend, batch=batch)


def _key_gravnet_block(op, n_rows, backend, batch):
    from repro.tuning.cache import (gravnet_block_int8_key,
                                    gravnet_block_key)
    if op.precision == "int8":
        return gravnet_block_int8_key(n_rows, op.attrs["d_hidden"],
                                      op.attrs["d_f"], op.attrs["k"],
                                      backend, batch=batch)
    return gravnet_block_key(n_rows, op.attrs["d_hidden"],
                             op.attrs["d_f"], op.attrs["k"],
                             "float32", backend, batch=batch)


def _key_attention(op, n_rows, backend, batch):
    # the executor launches one (B, N, d) flash call per micro-batch:
    # bh = the packed batch, s = t = n_rows
    from repro.tuning.cache import flash_attention_key
    return flash_attention_key(batch, n_rows, n_rows, op.out_dim or 128,
                               "float32", backend)


def _key_edge_aggregate(op, n_rows, backend, batch):
    from repro.tuning.cache import edge_aggregate_key
    return edge_aggregate_key(n_rows, _n_edges(op, n_rows),
                              op.out_dim or 1, "float32", backend,
                              batch=batch)


def _key_knn_build(op, n_rows, backend, batch):
    from repro.tuning.cache import knn_build_key
    return knn_build_key(n_rows, op.attrs["d_s"], op.attrs["k"],
                         "float32", backend, batch=batch)


def _key_knn_aggregate(op, n_rows, backend, batch):
    from repro.tuning.cache import knn_aggregate_key
    return knn_aggregate_key(n_rows, op.attrs["d_f"], op.attrs["k"],
                             "float32", backend, batch=batch)


# templates whose binder/tuning key is picked by the *template* the
# mapper chose, not the op type (a dense on the xla target has no
# tuning problem; the same dense on the MXU does)
TEMPLATE_BINDERS = {"fused_dense": _bind_fused_dense}
TEMPLATE_TUNING_KEYS = {"fused_dense": _key_fused_dense}


def bind_kernels(op, ctx: BindContext) -> None:
    """Kernel-opt dispatch for one op: template binder first, then the
    op-type binder from its spec."""
    binder = TEMPLATE_BINDERS.get(op.template)
    if binder is not None:
        binder(op, ctx)
        return
    spec = require_spec(op)
    if spec.bind is not None:
        spec.bind(op, ctx)


def tuning_problem(op, *, n_rows: int, backend: str, batch: int = 1):
    """The tuning-cache key this op's bound kernel launches with, or
    None for ops with no searchable launch config."""
    keyer = TEMPLATE_TUNING_KEYS.get(op.template)
    if keyer is None:
        keyer = require_spec(op).tuning_key
    if keyer is None:
        return None
    return keyer(op, n_rows, backend, batch)


# ========================================================================
# the registry
# ========================================================================
def _both(template: str) -> dict[str, str]:
    return {"mxu": template, "xla": template}


register_op(OpSpec(
    "input", templates={"xla": "io"}, infer=_infer_input))
register_op(OpSpec(
    "output", templates={"xla": "io"}, infer=_infer_output))
register_op(OpSpec(
    "linear", regular=True,
    templates={"mxu": "fused_dense", "xla": "xla_dense"},
    infer=_infer_dense, cost=_cost_dense, mxu_matmul=True,
    mxu_eff=_eff_dense))
register_op(OpSpec(
    "dense", regular=True,
    templates={"mxu": "fused_dense", "xla": "xla_dense"},
    infer=_infer_dense, cost=_cost_dense, mxu_matmul=True,
    mxu_eff=_eff_dense, int8_passthrough=True))
register_op(OpSpec(
    "relu", regular=True, templates=_both("xla_eltwise"),
    infer=_infer_same, cost=_cost_eltwise_like, int8_passthrough=True))
register_op(OpSpec(
    "concat", regular=True, templates=_both("xla_concat"),
    infer=_infer_concat, cost=_cost_eltwise_like, int8_passthrough=True))
register_op(OpSpec(
    "slice", regular=True, templates=_both("xla_slice"),
    infer=_infer_slice, cost=_cost_eltwise_like, int8_passthrough=True))
register_op(OpSpec(
    "retile", regular=True, templates=_both("xla_retile"),
    infer=_infer_retile, cost=_cost_eltwise_like))
register_op(OpSpec(
    "quant", regular=True, templates=_both("xla_quant"),
    infer=_infer_same, cost=_cost_eltwise_like))
register_op(OpSpec(
    "dequant", regular=True, templates=_both("xla_quant"),
    infer=_infer_same, cost=_cost_eltwise_like))
register_op(OpSpec(
    "attention", regular=True,
    templates={"mxu": "flash_attention", "xla": "xla_attention"},
    infer=_infer_attention, cost=_cost_attention, mxu_matmul=True,
    mxu_eff=_eff_attention, bind=_bind_attention,
    tuning_key=_key_attention))
register_op(OpSpec(
    "gravnet_aggregate", tpu_native_regular=True,
    templates={"mxu": "gravnet_kernel", "xla": "xla_gravnet"},
    infer=_infer_gravnet_aggregate, cost=_cost_gravnet_aggregate,
    mxu_matmul=True, mxu_eff=_eff_gravnet,
    bind=_bind_gravnet_aggregate, tuning_key=_key_gravnet_aggregate))
register_op(OpSpec(
    # the fused dense→aggregate→dense megakernel carries the
    # aggregation's data-dependent selection, so it classifies exactly
    # like gravnet_aggregate: irregular faithfully, regular under the
    # TPU-native reformulation
    "gravnet_block", tpu_native_regular=True,
    templates={"mxu": "gravnet_block_kernel", "xla": "xla_gravnet_block"},
    infer=_infer_gravnet_block, cost=_cost_gravnet_block,
    mxu_matmul=True, mxu_eff=_eff_gravnet,
    bind=_bind_gravnet_block, tuning_key=_key_gravnet_block))
register_op(OpSpec(
    "cps", templates=_both("xla_cps"),
    infer=_infer_cps, cost=_cost_cps))

# --- ragged / padding-free event path (passes/ragged.py) ----------------
register_op(OpSpec(
    # neighbor selection over bin-packed ragged events: data-dependent
    # like gravnet_aggregate, and regular under the same TPU-native
    # reformulation (iterated argmin over a dense distance matrix).
    # Both templates exchange COMPACT tensors — the op's value is an
    # (idx, d2) index tuple, which no retile may ever land on (see
    # passes/mapping.py).
    "knn_build", tpu_native_regular=True,
    templates={"mxu": "knn_build_kernel", "xla": "xla_knn_build"},
    infer=_infer_knn_build, cost=_cost_knn_build,
    mxu_matmul=True, mxu_eff=_eff_gravnet,
    bind=_bind_knn_build, tuning_key=_key_knn_build))
register_op(OpSpec(
    # Gaussian-potential aggregation over knn_build's indices: one-hot
    # selection matmuls, same classification as gravnet_aggregate.
    # Compact layout on both targets (its knn input is a tuple).
    "knn_aggregate", tpu_native_regular=True,
    templates={"mxu": "knn_agg_kernel", "xla": "xla_knn_agg"},
    infer=_infer_knn_aggregate, cost=_cost_knn_aggregate,
    mxu_matmul=True, mxu_eff=_eff_gravnet,
    bind=_bind_knn_aggregate, tuning_key=_key_knn_aggregate))

# --- edge-based message passing (GatedGCN / GraphSAGE family) -----------
register_op(OpSpec(
    # data-dependent gather of node rows by an explicit edge list —
    # irregular, like the kNN gather
    "gather_edge", templates=_both("xla_gather"),
    infer=_infer_gather_edge, cost=_cost_gather_edge))
register_op(OpSpec(
    # masked segment-sum/mean of per-edge messages into node slots; the
    # one-hot-matmul Pallas kernel (kernels/edge_aggregate.py) makes it
    # statically schedulable, so like gravnet_aggregate it reclassifies
    # as regular under tpu_native_gravnet
    "edge_aggregate", tpu_native_regular=True,
    templates={"mxu": "edge_aggregate_kernel",
               "xla": "xla_edge_aggregate"},
    infer=_infer_edge_aggregate, cost=_cost_edge_aggregate,
    bind=_bind_edge_aggregate, tuning_key=_key_edge_aggregate))
register_op(OpSpec(
    "eltwise", regular=True, templates=_both("xla_eltwise"),
    infer=_infer_eltwise, cost=_cost_eltwise_like))
register_op(OpSpec(
    "batchnorm", regular=True, templates=_both("xla_batchnorm"),
    infer=_infer_batchnorm, cost=_cost_batchnorm))
