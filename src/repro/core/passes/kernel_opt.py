"""Kernel-level optimization pass (paper §III-A "Kernel-Level Optimizations").

The paper's insight: at trigger-scale matrix sizes, per-iteration loop
scheduling overhead dominates kernel runtime, so they replace AIE loop
pipelining with loop *flattening* (``chess_flatten_loop``), trading program
memory for issue efficiency. Design ③ applies exactly this at identical
resource allocation.

TPU analogues applied here (design ③):

1. **Kernel binding** — every op's launch knobs are bound by the binder
   its registry spec declares (``op_registry.bind_kernels``): MXU dense
   ops below a size threshold switch from the grid-looped Pallas variant
   to the single-cell 'flattened' variant (whole operand in VMEM, no K
   loop), larger ops get tuned (bm, bn, bk) block shapes; gravnet /
   gravnet_block / edge_aggregate / attention bind cache-only knobs.
2. **Retile cancellation / layout propagation** — adjacent retiles that
   undo each other (lane128 → compact → lane128) are bypassed so a chain
   of MXU kernels hands tensors over in padded layout without copies.
3. **Int8 chain fusion** — inside an 8-bit partition, a dense feeding
   another dense emits int8 directly (requantized in the epilogue with
   the consumer's input scale) instead of dequant→requant through f32;
   scales are folded (the paper's bit-exact 8-bit interior handoff).
   Which consumers may sit on an 8-bit handoff is declared per op spec
   (``OpSpec.int8_passthrough``).
4. **Whole-pipeline jit** — the executor compiles the entire graph as one
   XLA program instead of one dispatch per segment (removes the
   heterogeneous-boundary overhead the paper measured in design ①).

Variant/block selection consults the persistent tuning cache
(``repro.tuning``) when one is supplied: a cached winner for the exact
(kernel, shape, dtype, backend) problem overrides the heuristic,
because LL-GNN-style studies show the latency-optimal config is
shape-dependent and must be searched. With no cache (or on any miss)
the heuristic is used unchanged — an empty cache reproduces today's
bindings bit-for-bit (tested).
"""
from __future__ import annotations

from repro.core.graph_ir import Graph
from repro.core.op_registry import BindContext, bind_kernels, op_spec

FLATTEN_ROWS = 512        # rows (hits × microbatch) below which we flatten
FLATTEN_DIM = 1024        # max feature dim for the flattened variant

_FUSED_DENSE_KNOBS = ("variant", "bm", "bn", "bk")


def _pick_block(v: int, cap: int) -> int:
    p = 1
    while p * 2 <= min(v, cap):
        p *= 2
    return p


def _tile(v: int, cap: int, align: int) -> int:
    """A Mosaic-legal tile for one block dimension: the whole dimension
    when it is no wider than one ``align`` tile, else the largest
    power of two up to ``cap`` (a multiple of ``align``). The TPU
    lowering requires a block's last two dims to be multiples of
    (8, 128) or equal to the (padded) array's; a power of two below
    the alignment would pad the array past the block and be refused."""
    return v if v <= align else _pick_block(v, cap)


def fused_dense_default(rows: int, d_in: int, d_out: int) -> dict:
    """The untuned fused-dense binding: flattened for trigger-scale
    matmuls, else the looped variant with tiles that compile on the
    TPU (rows on 8-row sublanes, d_in/d_out on 128-wide lanes)."""
    if rows <= FLATTEN_ROWS and max(d_in, d_out) <= FLATTEN_DIM:
        return {"variant": "flattened"}
    return {"variant": "looped", "bm": _tile(rows, 512, 8),
            "bn": _tile(d_out, 512, 128), "bk": _tile(d_in, 2048, 128)}


def fused_dense_shape(op, n_rows: int, batch: int = 1) -> tuple[int, int, int]:
    """(rows, d_in, d_out) of the matmul this op launches per step —
    the tuning-cache problem shape (shared with the autotuner).

    ``batch`` is the micro-batch width of a *batch-packed* executable
    (occupancy-bucketed serving): dense kernels row-pack events, so the
    batch dimension folds into ``rows`` (one launch sees batch·n_rows
    rows). ``batch=1`` is the legacy per-step shape, where rows scale
    with the segment's spatial parallelization P instead."""
    d_in = op.params["w"].shape[0]
    d_out = op.out_dim or op.params["w"].shape[1]
    if batch > 1:
        rows = n_rows * batch
    else:
        rows = n_rows * op.attrs_opt.get("P", 1)
    return rows, d_in, d_out


def fused_dense_dtype(op) -> str:
    """The dtype the executor will actually run this dense in."""
    if op.precision == "int8":
        return "int8"
    if op.precision == "bf16":
        return "bf16"
    return "float32"


def kernel_optimize(g: Graph, *, n_rows: int = 128, batch: int = 1,
                    tuning_cache=None, backend: str = "xla") -> Graph:
    """``n_rows`` is the per-event graph size (the occupancy bucket when
    bucketed); ``batch`` the packed micro-batch width (1 = per-event
    executable, unchanged legacy bindings and cache keys)."""
    g = g.clone()

    # 1. per-op kernel binding, dispatched through the registry
    # (cached winner > heuristic; cache-only binders leave a miss
    # untouched → identical bindings)
    ctx = BindContext(n_rows=n_rows, batch=batch, cache=tuning_cache,
                      backend=backend)
    for op in g:
        bind_kernels(op, ctx)

    # 2. retile cancellation: retile(B->A) after retile(A->B) bypasses both
    changed = True
    while changed:
        changed = False
        for op in list(g):
            if op.op_type != "retile":
                continue
            src = g[op.inputs[0]]
            if (src.op_type == "retile"
                    and src.attrs["from"] == op.attrs["to"]
                    and src.attrs["to"] == op.attrs["from"]):
                g.rewire(op.name, src.inputs[0])
                if not g.successors(op.name):
                    g.remove(op.name)
                if not g.successors(src.name):
                    g.remove(src.name)
                changed = True
                break

    # 3. int8 chain fusion: a dense may emit int8 straight into
    # consumers whose specs declare an 8-bit passthrough
    for op in g:
        if op.precision != "int8" or op.op_type != "dense":
            continue
        succ = g.successors(op.name)
        if succ and all(s.precision == "int8"
                        and getattr(op_spec(s.op_type),
                                    "int8_passthrough", False)
                        for s in succ):
            op.attrs_opt["emit_int8"] = True

    # 4. whole-pipeline jit
    g.meta["fuse_pipeline"] = True
    return g
