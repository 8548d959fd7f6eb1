"""Non-regression contract for the pattern-keyed pass refactor.

The deploy passes dispatch on registered op patterns
(``repro.core.op_registry``) instead of hard-coding CaloClusterNet's
shape. The contract of that refactor is that CaloClusterNet's deploy
path did not move: ``tests/golden/ccn_flow.json`` pins the pass-emitted
graphs (op names, templates, targets, segments, precisions, binding
knobs, calibrated scales) and the tuning-cache keys for every deploy
mode, and ``tests/golden/ccn_flow_outputs.npz`` pins the fused f32 and
calibrated int8 outputs. Structure, knobs, keys, decisions and counts
compare exactly; calibrated scales and float outputs within f32
tolerance, since their last bits belong to the XLA CPU build that ran
them. The JSON's ``_meta`` records the jax version that generated both
fixtures: parameters come from ``jax.random``, so a jax whose PRNG
changed needs them regenerated.

Regenerate (after an *intentional* flow change) with:

    REPRO_REGEN_GOLDEN=1 PYTHONPATH=src python -m pytest \
        tests/test_pattern_registry.py -q
"""
import json
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _numerics import assert_bitwise, assert_close

from repro.core import caloclusternet as ccn
from repro.core.graph_ir import Graph, Operator
from repro.core.passes.parallelize import Requirements
from repro.core.pipeline import deploy
from repro.tuning.autotune import graph_kernel_problems

GOLDEN_DIR = pathlib.Path(__file__).parent / "golden"
FLOW_JSON = GOLDEN_DIR / "ccn_flow.json"
OUT_NPZ = GOLDEN_DIR / "ccn_flow_outputs.npz"

CFG = ccn.CCNConfig(n_hits=32)

# every deploy mode whose emitted graph + tuning keys are pinned:
# (precision policy, fuse_gravnet_block, fuse_int8, needs calibration)
MODES = {
    "fp_fused": ("fp", True, True, False),
    "fp_unfused": ("fp", False, True, False),
    "mixed_fused": ("mixed", True, True, True),
    "mixed_unfused": ("mixed", True, False, True),
}


def _feeds():
    rng = np.random.default_rng(0)
    feats = jnp.asarray(rng.normal(size=(4, CFG.n_hits, CFG.d_in)),
                        jnp.float32)
    mask = jnp.asarray(rng.uniform(size=(4, CFG.n_hits)) < 0.7,
                       jnp.float32)
    return {"hits": feats, "mask": mask}


def _jsonable(v):
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    return None  # arrays / configs: identity is pinned via params+outputs


def _graph_record(g: Graph) -> list[dict]:
    return [{
        "name": op.name,
        "op_type": op.op_type,
        "inputs": list(op.inputs),
        "out_dim": op.out_dim,
        "target": op.target,
        "segment": op.segment,
        "precision": op.precision,
        "template": op.template,
        "attrs": {k: _jsonable(v) for k, v in sorted(op.attrs.items())},
        "attrs_opt": {k: _jsonable(v)
                      for k, v in sorted(op.attrs_opt.items())},
    } for op in g]


def _assert_record_match(got, want, where: str):
    """Exact structure; floats (calibrated scales) within f32
    tolerance."""
    if isinstance(want, float) and isinstance(got, float):
        assert_close(got, want, dtype="float32", context=where)
    elif isinstance(want, dict) and isinstance(got, dict):
        assert sorted(got) == sorted(want), f"{where} diverged"
        for k in want:
            _assert_record_match(got[k], want[k], f"{where}.{k}")
    elif isinstance(want, list) and isinstance(got, list):
        assert len(got) == len(want), f"{where} diverged"
        for i, (g, w) in enumerate(zip(got, want)):
            _assert_record_match(g, w, f"{where}[{i}]")
    else:
        assert got == want, f"{where} diverged: {got!r} != {want!r}"


def _key_record(g: Graph) -> dict:
    return {f"{backend}/batch{batch}": [
        k.encode() for k in graph_kernel_problems(
            g, n_rows=CFG.n_hits, backend=backend, batch=batch)]
        for backend in ("xla", "pallas") for batch in (1, 8)}


def _deploy(mode: str):
    policy, fuse_block, fuse_int8, calib = MODES[mode]
    req = Requirements(design_point=3, platform="cpu",
                       precision_policy=policy, n_hits=CFG.n_hits,
                       target_throughput=1e4)
    params = ccn.init(jax.random.PRNGKey(0), CFG)
    g = ccn.to_graph(params, CFG)
    feeds = _feeds()
    return deploy(g, req,
                  calibration_feeds=feeds if calib else None,
                  fuse_gravnet_block=fuse_block,
                  fuse_int8=fuse_int8), feeds


def _flatten_out(prefix: str, out: dict, into: dict):
    for k, v in out.items():
        if isinstance(v, dict):
            _flatten_out(f"{prefix}.{k}", v, into)
        else:
            into[f"{prefix}.{k}"] = np.asarray(v)


def _capture():
    flow = {}
    arrays: dict[str, np.ndarray] = {}
    for mode in MODES:
        pipe, feeds = _deploy(mode)
        flow[mode] = {"graph": _graph_record(pipe.graph),
                      "tuning_keys": _key_record(pipe.graph)}
        if mode in ("fp_fused", "mixed_fused"):
            _flatten_out(mode, pipe(feeds), arrays)
    return flow, arrays


@pytest.fixture(scope="module")
def golden():
    if os.environ.get("REPRO_REGEN_GOLDEN") == "1":
        GOLDEN_DIR.mkdir(parents=True, exist_ok=True)
        flow, arrays = _capture()
        flow["_meta"] = {"jax": jax.__version__}
        with open(FLOW_JSON, "w") as f:
            json.dump(flow, f, indent=1, sort_keys=True)
            f.write("\n")
        np.savez(OUT_NPZ, **arrays)
    if not (FLOW_JSON.exists() and OUT_NPZ.exists()):
        pytest.fail(f"missing golden fixtures under {GOLDEN_DIR}; "
                    "regenerate with REPRO_REGEN_GOLDEN=1")
    with open(FLOW_JSON) as f:
        flow = json.load(f)
    with np.load(OUT_NPZ) as z:
        arrays = {k: z[k] for k in z.files}
    return flow, arrays


@pytest.fixture(scope="module")
def fresh():
    flow, arrays = _capture()
    # normalize through the same JSON round-trip the fixture took
    return json.loads(json.dumps(flow)), arrays


@pytest.mark.parametrize("mode", sorted(MODES))
def test_graph_matches_golden(mode, golden, fresh):
    """Pass-emitted graphs (names, templates, targets, segments,
    precisions, binding knobs) are identical to the pre-refactor flow."""
    want = golden[0][mode]["graph"]
    got = fresh[0][mode]["graph"]
    assert [o["name"] for o in got] == [o["name"] for o in want]
    for w, g in zip(want, got):
        _assert_record_match(g, w, f"{mode}: op {w['name']}")


@pytest.mark.parametrize("mode", sorted(MODES))
def test_tuning_keys_match_golden(mode, golden, fresh):
    """Tuning-cache keys per backend/micro-batch are pinned: a renamed
    or re-shaped key would silently orphan every cached config."""
    assert fresh[0][mode]["tuning_keys"] == golden[0][mode]["tuning_keys"]


def test_outputs_bitwise_identical(golden, fresh):
    """Fused f32 and calibrated int8 deployed outputs reproduce the
    fixture: trigger decisions and cluster counts exactly, float heads
    and cluster features within f32 tolerance up to the hits whose
    k-NN selection flips at a near-tie."""
    want, got = golden[1], fresh[1]
    assert set(got) == set(want)
    for name in sorted(want):
        if np.issubdtype(want[name].dtype, np.floating):
            assert_close(got[name], want[name], dtype="float32",
                         context=name)
        else:
            assert_bitwise(got[name], want[name], context=name)


# ----------------------------------------------- unknown-op diagnostics ----
def test_deploy_rejects_unknown_op_with_actionable_error():
    """A graph holding an op no pass recognizes fails fast with the op
    type and node name in the message, not a deep KeyError."""
    g = Graph()
    g.add(Operator(name="hits", op_type="input", out_dim=4,
                   attrs={"feature": "hits"}))
    g.add(Operator(name="mystery", op_type="hyperbolic_conv",
                   inputs=["hits"], out_dim=4))
    g.add(Operator(name="out", op_type="output", inputs=["mystery"],
                   attrs={"head_names": ["y"]}, out_dim=4))
    req = Requirements(design_point=3, platform="cpu",
                       precision_policy="fp", n_hits=8,
                       target_throughput=1e3)
    with pytest.raises(Exception) as exc:
        deploy(g, req)
    msg = str(exc.value)
    assert "hyperbolic_conv" in msg and "mystery" in msg
