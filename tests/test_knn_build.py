"""Differential tests for the ragged kNN kernel pair.

``knn_build`` (segment-masked neighbor selection) is pinned **bitwise**
against the jnp oracle ``knn_build_ref`` — both run the same iterated
argmin with ties broken toward the lowest column index, so idx and d2
must agree exactly, on every backend. ``knn_aggregate`` runs the same
sequential per-slot accumulation as its oracle, but XLA's multiply-add
fusion may move last ulps between compilations, so the aggregation
claim is tolerance-level (``_numerics.DTYPE_TOLERANCES``). Batched vs.
per-bin launches share one cell body and are compared bitwise. A
golden fixture freezes today's selection order; tuning-key /
candidate / warm-up coverage mirrors the other kernel families.

Regenerate the fixture (after an *intentional* contract change) with:

    REPRO_REGEN_GOLDEN=1 PYTHONPATH=src python -m pytest \
        tests/test_knn_build.py -q
"""
from __future__ import annotations

import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _numerics import assert_bitwise, assert_close, backend_sweep

from repro.kernels import ops
from repro.kernels.ref import knn_aggregate_ref, knn_build_ref

jax.config.update("jax_platform_name", "cpu")

GOLDEN = pathlib.Path(__file__).parent / "golden" / "knn_build.npz"

_N, _DS, _DF, _K, _SEED = 32, 4, 10, 6, 2026


def _problem(n=_N, ds=_DS, df=_DF, *, seed=0, events=3, batch=None):
    """A bin-packed problem: ``events`` contiguous segments first (in
    order, like ``bin_pack`` lays them out), then a −1 padding tail."""
    rng = np.random.default_rng(seed)
    b = batch or 1
    seg = np.full((b, n), -1, np.int32)
    for i in range(b):
        cuts = np.sort(rng.integers(1, n, size=events - 1))
        fill = int(rng.integers(n // 2, n + 1))
        seg[i, :fill] = np.searchsorted(cuts, np.arange(fill),
                                        side="right")
    s = rng.normal(size=(b, n, ds)).astype(np.float32)
    f = rng.normal(size=(b, n, df)).astype(np.float32)
    if batch is None:
        return jnp.asarray(s[0]), jnp.asarray(f[0]), jnp.asarray(seg[0])
    return jnp.asarray(s), jnp.asarray(f), jnp.asarray(seg)


# ------------------------------------------------------- kernel vs oracle ----
@pytest.mark.parametrize("backend", backend_sweep())
@pytest.mark.parametrize("k", [2, 6])
def test_build_matches_ref_bitwise(backend, k):
    s, _, seg = _problem(seed=1)
    want_idx, want_d2 = knn_build_ref(s, seg, k=k)
    idx, d2 = ops.knn_build(s, seg, k=k, backend=backend)
    assert_bitwise(idx, want_idx, context=f"{backend}/k={k}/idx")
    assert_bitwise(d2, want_d2, context=f"{backend}/k={k}/d2")


@pytest.mark.parametrize("backend", backend_sweep())
def test_aggregate_matches_ref(backend):
    s, f, seg = _problem(seed=2)
    idx, d2 = knn_build_ref(s, seg, k=_K)
    want = knn_aggregate_ref(f, idx, d2, scale=10.0)
    got = ops.knn_aggregate(f, idx, d2, scale=10.0, backend=backend)
    assert_close(got, want, dtype="float32", context=backend)


def test_tie_break_is_lowest_column_index():
    """Two equidistant candidates: the selection must take the lower
    row index first — the pinned contract that makes bin packing
    order-preserving (and ragged == padded tie-for-tie)."""
    s = jnp.asarray([[0.0], [1.0], [-1.0], [1.0]], jnp.float32)
    seg = jnp.zeros((4,), jnp.int32)
    idx, d2 = knn_build_ref(s, seg, k=3)
    # row 0's candidates: rows 1, 2, 3 all at distance 1 -> order 1,2,3
    np.testing.assert_array_equal(np.asarray(idx[0]), [1, 2, 3])
    np.testing.assert_array_equal(np.asarray(d2[0]), [1.0, 1.0, 1.0])
    for backend in backend_sweep():
        gi, gd = ops.knn_build(s, seg, k=3, bm=4, backend=backend)
        assert_bitwise(gi, idx, context=backend)
        assert_bitwise(gd, d2, context=backend)


def test_exhausted_slots_are_sentinels():
    """An event smaller than k+1 rows runs out of candidates: the
    remaining slots must carry the 1e30 sentinel the aggregation (and
    any downstream consumer) gates on."""
    s, _, _ = _problem(seed=3)
    seg = np.full((_N,), -1, np.int32)
    seg[:3] = 0          # one 3-hit event -> only 2 real neighbors
    idx, d2 = knn_build_ref(s, jnp.asarray(seg), k=_K)
    d2 = np.asarray(d2)
    assert (d2[:3, 2:] >= 0.5e30).all()
    assert (d2[:3, :2] < 0.5e30).all()
    assert (d2[3:] >= 0.5e30).all()   # padding rows select nothing


@pytest.mark.parametrize("backend", backend_sweep())
def test_cross_segment_selection_is_impossible(backend):
    s, _, seg = _problem(seed=4)
    idx, d2 = ops.knn_build(s, seg, k=_K, backend=backend)
    idx, d2, seg = np.asarray(idx), np.asarray(d2), np.asarray(seg)
    valid = d2 < 0.5e30
    rows, slots = np.nonzero(valid)
    assert rows.size                        # sanity: something selected
    np.testing.assert_array_equal(seg[idx[rows, slots]], seg[rows])
    assert (idx[rows, slots] != rows).all()  # self never selected


# -------------------------------------------------- batched vs per-bin ----
@pytest.mark.parametrize("backend", backend_sweep())
def test_batched_matches_per_bin_loop(backend):
    s, f, seg = _problem(seed=5, batch=4)
    bi, bd = ops.knn_build_batched(s, seg, k=_K, backend=backend)
    agg = ops.knn_aggregate_batched(f, bi, bd, scale=10.0,
                                    backend=backend)
    for i in range(s.shape[0]):
        wi, wd = ops.knn_build(s[i], seg[i], k=_K, backend=backend)
        assert_bitwise(bi[i], wi, context=f"{backend}/bin{i}/idx")
        assert_bitwise(bd[i], wd, context=f"{backend}/bin{i}/d2")
        wa = ops.knn_aggregate(f[i], wi, wd, scale=10.0, backend=backend)
        assert_bitwise(agg[i], wa, context=f"{backend}/bin{i}/agg")


@pytest.mark.parametrize("backend",
                         [b for b in backend_sweep() if b != "xla"])
def test_non_default_bm_is_bitwise(backend):
    """The row tile only splits the query axis; selection state is
    per-row, so every bm must reproduce the default bitwise."""
    s, f, seg = _problem(seed=6)
    idx0, d20 = ops.knn_build(s, seg, k=_K, backend=backend)
    agg0 = ops.knn_aggregate(f, idx0, d20, backend=backend)
    for bm in (8, 16):
        idx, d2 = ops.knn_build(s, seg, k=_K, bm=bm, backend=backend)
        assert_bitwise(idx, idx0, context=f"{backend}/bm={bm}")
        assert_bitwise(d2, d20, context=f"{backend}/bm={bm}")
        agg = ops.knn_aggregate(f, idx, d2, bm=bm, backend=backend)
        assert_bitwise(agg, agg0, context=f"{backend}/bm={bm}/agg")


# ----------------------------------------------------------- golden ----
def _generate() -> dict:
    s, f, seg = _problem(seed=_SEED)
    idx, d2 = knn_build_ref(s, seg, k=_K)
    agg = knn_aggregate_ref(f, idx, d2, scale=10.0)
    return dict(s=np.asarray(s), f=np.asarray(f), seg=np.asarray(seg),
                k=np.int32(_K), idx=np.asarray(idx), d2=np.asarray(d2),
                agg=np.asarray(agg))


@pytest.fixture(scope="module")
def golden():
    if os.environ.get("REPRO_REGEN_GOLDEN") == "1":
        GOLDEN.parent.mkdir(parents=True, exist_ok=True)
        np.savez(GOLDEN, **_generate())
    if not GOLDEN.exists():
        pytest.fail(f"missing golden fixture {GOLDEN}; regenerate with "
                    "REPRO_REGEN_GOLDEN=1")
    with np.load(GOLDEN) as z:
        return {k: z[k] for k in z.files}


def test_golden_fixture_is_current(golden):
    fresh = _generate()
    assert set(fresh) == set(golden)
    for name, arr in fresh.items():
        np.testing.assert_array_equal(arr, golden[name], err_msg=name)


@pytest.mark.parametrize("backend", backend_sweep())
def test_kernels_match_golden(backend, golden):
    """Selection order (idx — exact) and aggregation (tolerance)
    against the committed fixture: any change to the tie-break
    contract shows up as an index diff. The fixture's distances are the
    reference's own, which the xla backend reproduces bitwise; a Pallas
    body computes them in its own order (f32 tolerance)."""
    idx, d2 = ops.knn_build(jnp.asarray(golden["s"]),
                            jnp.asarray(golden["seg"]),
                            k=int(golden["k"]), backend=backend)
    assert_bitwise(idx, golden["idx"], context=f"{backend}/idx")
    if backend == "xla":
        assert_bitwise(d2, golden["d2"], context=f"{backend}/d2")
    else:
        assert_close(d2, golden["d2"], dtype="float32",
                     context=f"{backend}/d2")
    agg = ops.knn_aggregate(jnp.asarray(golden["f"]), idx, d2,
                            scale=10.0, backend=backend)
    assert_close(agg, golden["agg"], dtype="float32", context=backend)


# ------------------------------------------------- tuning integration ----
def test_tuning_keys_and_candidates():
    from repro.tuning import knn_aggregate_key, knn_build_key
    from repro.tuning.candidates import (default_knn_aggregate,
                                         default_knn_build,
                                         knn_aggregate_candidates,
                                         knn_build_candidates)
    k1 = knn_build_key(32, 4, 8, "float32", "xla")
    assert k1.encode() == "knn_build|32x4x8|float32|xla"
    kb = knn_build_key(32, 4, 8, "float32", "xla", batch=8)
    assert kb.encode() == "knn_build|8x32x4x8|float32|xla"
    ka = knn_aggregate_key(32, 22, 8, "float32", "pallas", batch=8)
    assert ka.encode() == "knn_aggregate|8x32x22x8|float32|pallas"
    for cands, default in ((knn_build_candidates(32),
                            default_knn_build(32)),
                           (knn_aggregate_candidates(32),
                            default_knn_aggregate(32))):
        assert cands[0] == default        # heuristic default leads
        assert all(32 % c["bm"] == 0 for c in cands)
        assert len(cands) == len({tuple(sorted(c.items()))
                                  for c in cands})


def test_autotune_records_winners(tmp_path):
    from repro.tuning import TuningCache, knn_aggregate_key
    from repro.tuning.autotune import tune_knn_aggregate, tune_knn_build
    cache = TuningCache(tmp_path / "tc.json")
    cfg = tune_knn_build(16, 4, 4, dtype="float32", backend="xla",
                         cache=cache, iters=1)
    assert "bm" in cfg and len(cache) == 1
    cfg = tune_knn_aggregate(16, 8, 4, scale=7.5, dtype="float32",
                             backend="xla", cache=cache, iters=1)
    assert "scale" not in cfg             # the binder reads knobs only
    entry = cache.entry(knn_aggregate_key(16, 8, 4, "float32", "xla"))
    assert entry.config["scale"] == 7.5   # …but warm-up can replay it
    assert len(cache) == 2


def test_warmup_replays_knn_entries():
    from repro.tuning import (TuningCache, knn_aggregate_key,
                              knn_build_key, warm_from_cache)
    cache = TuningCache()
    cache.put(knn_build_key(16, 4, 4, "float32", "xla"), {"bm": 16})
    cache.put(knn_build_key(16, 4, 4, "float32", "xla", batch=2),
              {"bm": 16})
    cache.put(knn_aggregate_key(16, 8, 4, "float32", "xla"),
              {"bm": 16, "scale": 5.0})
    assert warm_from_cache(cache) == 3
    assert warm_from_cache(cache, kernels=("knn_build",)) == 2


def test_deployed_graph_emits_knn_problems():
    """The raggedized deploy graph advertises knn tuning problems with
    the batched (bins-leading) shapes — the five-way agreement between
    registry, cache keys, candidates, autotuner, and warm-up."""
    import repro.core.caloclusternet as ccn
    from repro.core.pipeline import Requirements, deploy
    cfg = ccn.current_detector_config()
    params = ccn.init(jax.random.PRNGKey(0), cfg)
    g = ccn.to_graph(params, cfg)
    req = Requirements(design_point=3, platform="cpu",
                       precision_policy="fp", n_hits=cfg.n_hits,
                       target_throughput=5e4, max_latency_s=2e-3)
    rp = deploy(g, req, batch=4, ragged=True,
                fuse_gravnet_block=False)
    from repro.tuning.autotune import graph_kernel_problems
    probs = graph_kernel_problems(rp.pipe.graph, n_rows=cfg.n_hits,
                                  backend="xla", batch=4)
    kinds = {p.kernel for p in probs}
    assert "knn_build" in kinds and "knn_aggregate" in kinds
    for p in probs:
        if p.kernel.startswith("knn_"):
            assert p.shape[0] == 4        # bins-leading batched shape
            assert p.shape[1] == cfg.n_hits
