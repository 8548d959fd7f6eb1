"""Compile the served path for a TPU v5e that is described, not attached.

Interpret mode runs a Pallas kernel body on the CPU but never asks the
TPU's compiler; block shapes off the (8, 128) tiling, VMEM overruns and
unpartitionable kernels surface only here. Each case lowers a kernel
wrapper (``backend="pallas"``) or a deployed pipeline's launch at the
paper's upgrade-detector widths — n_hits 128, d_hidden 64, d_s 4,
d_flr 22, k 8 — onto one chip of a ``v5e:2x2`` topology, compiles it,
and checks the program holds the Mosaic kernel (``tpu_custom_call``).
Nothing runs, so these say nothing about results or times.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import caloclusternet as ccn
from repro.core.graph_ir import export_graph
from repro.core.passes.kernel_opt import fused_dense_default
from repro.core.passes.parallelize import Requirements
from repro.core.pipeline import deploy
from repro.kernels import ops
from repro.launch.mesh import V5E

N, DH, DS, DF, K, B = 128, 64, 4, 22, 8, 8      # CCNConfig() widths
DCAT = DH + 2 * DF


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a described chip's executables cannot be read back from a
    # persistent cache: keep it out of these compiles
    prev = jax.config.jax_enable_compilation_cache

    def restore():
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler installed
        restore()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    try:
        yield desc
    finally:
        restore()


@pytest.fixture(scope="module")
def spec(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


def _lead(batched):
    return (B,) if batched else ()


# ------------------------------------------------------------- kernels ----
@pytest.mark.parametrize("batched", [False, True], ids=["event", "batch8"])
@pytest.mark.parametrize("dtype", ["f32", "int8"])
def test_gravnet_block_compiles(spec, dtype, batched):
    lead = _lead(batched)
    x, mask = spec(lead + (N, DH)), spec(lead + (N,))
    if dtype == "f32":
        fn = ops.gravnet_block_batched if batched else ops.gravnet_block
        args = (x, mask, spec((DH, DS)), spec((DS,)), spec((DH, DF)),
                spec((DF,)), spec((DCAT, DH)), spec((DH,)))
        kw = {}
    else:
        fn = (ops.gravnet_block_int8_batched if batched
              else ops.gravnet_block_int8)
        i8 = jnp.int8
        args = (x, mask, spec((DH, DS), i8), spec((DS,)),
                spec((DH, DF), i8), spec((DF,)), spec((DCAT, DH), i8),
                spec((DH,)), spec((DS,)), spec((DF,)), spec((DH,)))
        kw = dict(x_scale=0.02, agg_scale=0.01, h_scale=0.03)
    _assert_kernel(fn.lower(*args, k=K, activation="relu",
                            backend="pallas", **kw))


@pytest.mark.parametrize("batched", [False, True], ids=["event", "batch8"])
def test_knn_build_compiles(spec, batched):
    lead = _lead(batched)
    fn = ops.knn_build_batched if batched else ops.knn_build
    _assert_kernel(fn.lower(spec(lead + (N, DS)),
                            spec(lead + (N,), jnp.int32), k=K,
                            backend="pallas"))


@pytest.mark.parametrize("batched", [False, True], ids=["event", "batch8"])
def test_knn_aggregate_compiles(spec, batched):
    lead = _lead(batched)
    fn = ops.knn_aggregate_batched if batched else ops.knn_aggregate
    _assert_kernel(fn.lower(spec(lead + (N, DF)),
                            spec(lead + (N, K), jnp.int32),
                            spec(lead + (N, K)), backend="pallas"))


@pytest.mark.parametrize("batched", [False, True], ids=["event", "batch8"])
def test_edge_aggregate_compiles(spec, batched):
    # the serve.py edge-model widths: 64 nodes, E = 4N, d_hidden 32
    n, e, d = 64, 256, 32
    lead = _lead(batched)
    fn = ops.edge_aggregate_batched if batched else ops.edge_aggregate
    _assert_kernel(fn.lower(spec(lead + (e, d)),
                            spec(lead + (2, e), jnp.int32), n,
                            spec(lead + (e,)), backend="pallas"))


@pytest.mark.parametrize("d_in,d_out", [(32, 7), (108, 64), (64, 26)],
                         ids=["heads", "gravnet_out", "s_f_proj"])
def test_fused_dense_default_tiles_compile(spec, d_in, d_out):
    """The untuned looped binding at a batch-8 × 128-hit launch —
    narrow widths take the whole dimension instead of a power-of-two
    tile the TPU lowering refuses."""
    rows = B * N
    cfg = fused_dense_default(rows, d_in, d_out)
    assert cfg["variant"] == "looped"
    kw = {k: cfg[k] for k in ("bm", "bn", "bk")}
    _assert_kernel(ops.fused_dense.lower(
        spec((rows, d_in)), spec((d_in, d_out)), spec((d_out,)),
        activation="none", variant="looped", backend="pallas", **kw))


def test_flash_attention_compiles(spec):
    bh, s, d = 2, 256, 64
    _assert_kernel(ops.flash_attention.lower(
        spec((bh, s, d)), spec((bh, s, d)), spec((bh, s, d)),
        backend="pallas"))


# ------------------------------------------------ matmul precision ----
def _kernel_dots(jaxpr, inside=False):
    """Every ``dot_general`` inside a ``pallas_call`` of ``jaxpr``,
    nested jaxprs (jit, loops, the kernel body) included."""
    for eqn in jaxpr.eqns:
        if inside and eqn.primitive.name == "dot_general":
            yield eqn
        kernel = inside or eqn.primitive.name == "pallas_call"
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _kernel_dots(sub, kernel)


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


_I8 = jnp.int8
PRECISION_CASES = {
    "fused_dense_flattened": lambda: (ops.fused_dense, (
        _sds((N, DH)), _sds((DH, DF)), _sds((DF,))),
        dict(variant="flattened")),
    "fused_dense_looped": lambda: (ops.fused_dense, (
        _sds((B * N, DCAT)), _sds((DCAT, DH)), _sds((DH,))),
        dict(variant="looped")),
    "gravnet_aggregate": lambda: (ops.gravnet_aggregate, (
        _sds((N, DS)), _sds((N, DF)), _sds((N,))), dict(k=K)),
    "gravnet_block": lambda: (ops.gravnet_block, (
        _sds((N, DH)), _sds((N,)), _sds((DH, DS)), _sds((DS,)),
        _sds((DH, DF)), _sds((DF,)), _sds((DCAT, DH)), _sds((DH,))),
        dict(k=K)),
    "gravnet_block_int8": lambda: (ops.gravnet_block_int8, (
        _sds((N, DH)), _sds((N,)), _sds((DH, DS), _I8), _sds((DS,)),
        _sds((DH, DF), _I8), _sds((DF,)), _sds((DCAT, DH), _I8),
        _sds((DH,)), _sds((DS,)), _sds((DF,)), _sds((DH,))),
        dict(k=K, x_scale=0.02, agg_scale=0.01, h_scale=0.03)),
    "knn_build": lambda: (ops.knn_build, (
        _sds((N, DS)), _sds((N,), jnp.int32)), dict(k=K)),
    "knn_aggregate": lambda: (ops.knn_aggregate, (
        _sds((N, DF)), _sds((N, K), jnp.int32), _sds((N, K))), {}),
    "edge_aggregate": lambda: (ops.edge_aggregate, (
        _sds((256, 32)), _sds((2, 256), jnp.int32)), dict(n_nodes=64)),
    "flash_attention": lambda: (ops.flash_attention, (
        _sds((2, 256, 64)), _sds((2, 256, 64)), _sds((2, 256, 64))), {}),
}


@pytest.mark.parametrize("name", sorted(PRECISION_CASES))
def test_f32_kernel_matmuls_contract_at_full_f32(name):
    """Mosaic's default contraction rounds f32 operands to bf16; every
    kernel matmul on f32 operands asks for ``Precision.HIGHEST``, and
    one on int8 operands keeps the (exact) default. Traced on the CPU:
    the precision is a property of the kernel body, not of a chip."""
    fn, args, kw = PRECISION_CASES[name]()
    jaxpr = jax.make_jaxpr(
        lambda *a: fn(*a, backend="pallas", **kw))(*args).jaxpr
    dots = list(_kernel_dots(jaxpr))
    assert dots, f"{name}: no matmul inside a Pallas kernel"
    for eqn in dots:
        f32 = all(v.aval.dtype == jnp.float32 for v in eqn.invars)
        want = ((jax.lax.Precision.HIGHEST,) * 2 if f32 else None)
        assert eqn.params["precision"] == want, (
            name, [v.aval for v in eqn.invars], eqn.params["precision"])


# ------------------------------------------------- deployed pipelines ----
def _ccn_graph():
    cfg = ccn.CCNConfig()
    return export_graph("caloclusternet",
                        ccn.init(jax.random.PRNGKey(0), cfg), cfg), cfg


def _tpu_req(**kw):
    return Requirements(platform="tpu", device_kind=V5E, design_point=3,
                        n_hits=N, target_throughput=1e5,
                        max_latency_s=2e-3, **kw)


def _calibration(cfg, n=16):
    rng = np.random.default_rng(0)
    return {"hits": rng.normal(size=(n, cfg.n_hits, cfg.d_in))
            .astype(np.float32),
            "mask": (rng.uniform(size=(n, cfg.n_hits)) < 0.6)
            .astype(np.float32)}


@pytest.mark.parametrize("precision,batch", [("fp", 1), ("fp", 8),
                                             ("mixed", 1)])
def test_deployed_ccn_pipeline_compiles(spec, precision, batch):
    """The whole-pipeline launch ``deploy`` emits for the chip. fp at
    batch 8 × 128 hits is the bucketed path's largest bucket; mixed
    runs the calibrated int8 megakernel (calibration runs the jnp
    reference, so it needs no chip)."""
    g, cfg = _ccn_graph()
    pipe = deploy(g, _tpu_req(precision_policy=precision),
                  kernel_backend="pallas", batch=batch,
                  calibration_feeds=_calibration(cfg)
                  if precision == "mixed" else None)
    assert pipe.backend == "pallas"
    mb = pipe.microbatch
    _assert_kernel(pipe.lower({"hits": spec((mb, N, cfg.d_in)),
                               "mask": spec((mb, N))}))


def test_deployed_ragged_ccn_pipeline_compiles(spec):
    g, cfg = _ccn_graph()
    rp = deploy(g, _tpu_req(precision_policy="fp"),
                kernel_backend="pallas", batch=B, ragged=True)
    i32 = jnp.int32
    _assert_kernel(rp.pipe.lower({
        "hits": spec((B, N, cfg.d_in)), "mask": spec((B, N)),
        "segids": spec((B, N), i32), "slots": spec((B, N), i32)}))


def test_deployed_gatedgcn_pipeline_compiles(spec):
    """serve.py's GatedGCN route. Its P-search on the chip picks
    different P for the MXU and XLA segments, so the launch also
    exercises the chunked segment path."""
    from repro.models.gnn import gatedgcn
    gcfg = gatedgcn.GatedGCNConfig(n_layers=4, d_hidden=32, d_in=8,
                                   d_edge_in=4, n_classes=2)
    g = export_graph("gatedgcn",
                     gatedgcn.init(jax.random.PRNGKey(1), gcfg), gcfg)
    n, e = 64, 256
    pipe = deploy(g, dataclasses.replace(_tpu_req(precision_policy="fp"),
                                         n_hits=n),
                  kernel_backend="pallas")
    mb = pipe.microbatch
    _assert_kernel(pipe.lower({
        "nodes": spec((mb, n, gcfg.d_in)),
        "edge_index": spec((mb, 2, e), jnp.int32),
        "edges": spec((mb, e, gcfg.d_edge_in)),
        "node_mask": spec((mb, n)), "edge_mask": spec((mb, e))}))
