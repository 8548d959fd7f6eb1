"""Tuning cache + autotuner + regression gate tests.

Covers the hard invariants of the tuning subsystem:

- cache hit/miss semantics and JSON round-trip determinism;
- graceful fallback on missing / corrupt / stale cache files;
- ``kernel_optimize`` with an *empty* cache reproduces the heuristic
  bindings bit-for-bit (tuning is an overlay, never a behavior change);
- cached winners actually bind (and are marked as searched);
- replica warm-up replays cached shapes at startup (stale entries
  skipped and reported), and a warm-up that raises fails service start;
- the benchmark-regression comparator passes/fails correctly, and the
  harness runner exits nonzero on broken sections.
"""
from __future__ import annotations

import json
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))   # benchmarks/ lives at the repo root

from repro.core import caloclusternet as ccn
from repro.core.passes.kernel_opt import kernel_optimize
from repro.core.passes.mapping import map_templates
from repro.core.passes.partition import partition
from repro.core.quantization import apply_precision_policy
from repro.tuning import (SCHEMA_VERSION, KernelKey, TuningCache,
                          fused_dense_key, gravnet_key, make_warmup,
                          tune_fused_dense, warm_from_cache)
from repro.tuning.candidates import default_fused_dense


# ------------------------------------------------------------------ cache ----
def test_cache_hit_and_miss(tmp_path):
    cache = TuningCache(tmp_path / "tc.json")
    key = fused_dense_key(128, 64, 64, "float32", "xla")
    assert cache.lookup(key) is None                     # miss
    cache.put(key, {"variant": "flattened"}, us=12.5, candidates=3)
    assert cache.lookup(key) == {"variant": "flattened"}  # hit
    # a different backend/dtype/shape is a distinct problem
    assert cache.lookup(fused_dense_key(128, 64, 64, "int8", "xla")) is None
    assert cache.lookup(fused_dense_key(256, 64, 64, "float32", "xla")) is None
    assert key in cache and len(cache) == 1


def test_cache_round_trip_determinism(tmp_path):
    p = tmp_path / "tc.json"
    cache = TuningCache()
    cache.put(fused_dense_key(128, 64, 64, "int8", "xla"),
              {"variant": "looped", "bm": 32, "bn": 128, "bk": 128},
              us=60.0, default_us=100.0, candidates=4)
    cache.put(gravnet_key(128, 4, 22, 8, "float32", "xla"),
              {"bm": 64}, us=300.0, candidates=5)
    cache.save(p)
    first = p.read_bytes()
    loaded = TuningCache.load(p)
    assert loaded.load_error is None
    assert {k.encode() for k in loaded.entries()} \
        == {k.encode() for k in cache.entries()}
    for k, e in cache.entries().items():
        le = loaded.entry(k)
        assert le.config == e.config and le.us == e.us \
            and le.default_us == e.default_us \
            and le.candidates == e.candidates
    loaded.save(p)                       # re-serialize → byte-identical
    assert p.read_bytes() == first


def test_cache_key_encode_decode():
    key = KernelKey("flash_attention", (8, 512, 512, 64), "float32",
                    "pallas")
    assert KernelKey.decode(key.encode()) == key


def test_cache_missing_file_is_empty(tmp_path):
    cache = TuningCache.load(tmp_path / "nope.json")
    assert len(cache) == 0 and cache.load_error is None


def test_cache_corrupt_file_falls_back(tmp_path):
    p = tmp_path / "tc.json"
    p.write_text("{this is not json")
    cache = TuningCache.load(p)
    assert len(cache) == 0
    assert cache.load_error and "tc.json" in cache.load_error
    # wrong top-level type
    p.write_text("[1, 2, 3]")
    assert TuningCache.load(p).load_error is not None


def test_cache_stale_schema_ignored(tmp_path):
    p = tmp_path / "tc.json"
    p.write_text(json.dumps({
        "schema": SCHEMA_VERSION + 1,
        "entries": {"fused_dense|1x1x1|float32|xla":
                    {"config": {"variant": "flattened"}}},
    }))
    cache = TuningCache.load(p)
    assert len(cache) == 0 and "stale" in cache.load_error


def test_cache_skips_malformed_entries(tmp_path):
    p = tmp_path / "tc.json"
    good = fused_dense_key(64, 32, 32, "float32", "xla")
    p.write_text(json.dumps({
        "schema": SCHEMA_VERSION,
        "entries": {
            good.encode(): {"config": {"variant": "flattened"}},
            "garbage-key": {"config": {}},
            "fused_dense|1x2x3|f32|xla": "not-a-dict",
        },
    }))
    cache = TuningCache.load(p)
    assert cache.lookup(good) == {"variant": "flattened"}
    assert len(cache) == 1


# ------------------------------------------------------- kernel_opt overlay ----
def _optimized_graph(tuning_cache=None, backend="xla"):
    cfg = ccn.CCNConfig()
    params = ccn.init(jax.random.PRNGKey(0), cfg)
    g = ccn.to_graph(params, cfg)
    g = partition(g)
    g = apply_precision_policy(g, policy="mixed")
    g = map_templates(g)
    for op in g:
        op.attrs_opt["P"] = 1
    return cfg, kernel_optimize(g, n_rows=cfg.n_hits,
                                tuning_cache=tuning_cache, backend=backend)


def test_kernel_opt_empty_cache_bit_for_bit():
    """An empty cache must reproduce the heuristic bindings exactly."""
    _, g_none = _optimized_graph(tuning_cache=None)
    _, g_empty = _optimized_graph(tuning_cache=TuningCache())
    a = {op.name: dict(op.attrs_opt) for op in g_none}
    b = {op.name: dict(op.attrs_opt) for op in g_empty}
    assert a == b
    assert not any("tuned" in v for v in b.values())


def test_kernel_opt_binds_cached_winner():
    cfg = ccn.CCNConfig()
    cache = TuningCache()
    # seed a winner for every fused_dense problem + the gravnet row-tile
    from repro.core.passes.kernel_opt import (fused_dense_dtype,
                                              fused_dense_shape)
    _, g_heur = _optimized_graph(tuning_cache=None)
    tuned_cfg = {"variant": "looped", "bm": 32, "bn": 128, "bk": 128}
    for op in g_heur:
        if op.template == "fused_dense":
            rows, d_in, d_out = fused_dense_shape(op, cfg.n_hits)
            cache.put(fused_dense_key(rows, d_in, d_out,
                                      fused_dense_dtype(op), "xla"),
                      tuned_cfg)
        elif op.op_type == "gravnet_aggregate":
            cache.put(gravnet_key(cfg.n_hits, op.attrs["d_s"],
                                  op.attrs["d_f"], op.attrs["k"],
                                  "float32", "xla"), {"bm": 64})
    _, g = _optimized_graph(tuning_cache=cache)
    denses = [op for op in g if op.template == "fused_dense"]
    assert denses
    for op in denses:
        assert op.attrs_opt["variant"] == "looped"
        assert op.attrs_opt["bm"] == 32
        assert op.attrs_opt.get("tuned") is True
    gn = [op for op in g if op.op_type == "gravnet_aggregate"]
    assert gn and all(op.attrs_opt.get("bm") == 64 for op in gn)


def test_kernel_opt_cache_for_other_backend_is_a_miss():
    cfg = ccn.CCNConfig()
    cache = TuningCache()
    from repro.core.passes.kernel_opt import (fused_dense_dtype,
                                              fused_dense_shape)
    _, g_heur = _optimized_graph(tuning_cache=None)
    for op in g_heur:
        if op.template == "fused_dense":
            rows, d_in, d_out = fused_dense_shape(op, cfg.n_hits)
            cache.put(fused_dense_key(rows, d_in, d_out,
                                      fused_dense_dtype(op), "pallas"),
                      {"variant": "looped", "bm": 8, "bn": 128, "bk": 128})
    _, g = _optimized_graph(tuning_cache=cache, backend="xla")
    heur = {op.name: dict(op.attrs_opt) for op in g_heur}
    got = {op.name: dict(op.attrs_opt) for op in g}
    assert got == heur          # pallas entries never bind for xla


# -------------------------------------------------------------- autotuner ----
def test_tune_fused_dense_prefers_default_under_min_gain(tmp_path):
    """With an unreachable min_gain the searched winner must be exactly
    the heuristic default — noise can never de-tune the pipeline.
    (pallas_interpret: a backend where the launch knobs are live.)"""
    cache = TuningCache()
    cfg = tune_fused_dense(16, 8, 8, backend="pallas_interpret",
                           cache=cache, iters=1, min_gain=10.0)
    assert cfg == default_fused_dense(16, 8, 8)
    key = fused_dense_key(16, 8, 8, "float32", "pallas_interpret")
    entry = cache.entry(key)
    assert entry is not None and entry.candidates >= 2
    assert entry.us is not None and entry.default_us is not None


def test_tune_on_knob_inert_backend_records_default_only():
    """The 'xla' wrappers ignore variant/blocks, so searching there
    would record timer noise as winners: the tuner must pin the
    heuristic default and measure it once."""
    cache = TuningCache()
    cfg = tune_fused_dense(16, 8, 8, backend="xla", cache=cache, iters=1)
    assert cfg == default_fused_dense(16, 8, 8)
    entry = cache.entry(fused_dense_key(16, 8, 8, "float32", "xla"))
    assert entry.candidates == 1 and entry.us == entry.default_us


def test_tune_fused_dense_int8_default_is_executor_default():
    from repro.tuning.candidates import fused_dense_int8_candidates
    cands = fused_dense_int8_candidates(128, 64, 64)
    assert cands[0] == {"variant": "looped", "bm": 128, "bn": 128,
                       "bk": 512}
    assert all(c["variant"] == "looped" for c in cands)


# ----------------------------------------------------------------- warm-up ----
def test_warm_from_cache_replays_entries():
    cache = TuningCache()
    cache.put(fused_dense_key(16, 8, 8, "float32", "xla"),
              {"variant": "flattened"})
    cache.put(gravnet_key(16, 4, 6, 4, "float32", "xla"), {"bm": 16})
    # stale/impossible entry must be skipped, not raise
    cache.put(KernelKey("fused_dense", (16, 8), "float32", "xla"),
              {"variant": "flattened"})
    assert warm_from_cache(cache) == 2
    assert warm_from_cache(cache, backend="pallas") == 0
    assert warm_from_cache(cache, kernels=("gravnet",)) == 1


def test_replica_engine_runs_warmup_before_traffic():
    import numpy as np

    from repro.serving import ShardedTriggerService
    calls = []
    cache = TuningCache()
    cache.put(fused_dense_key(16, 8, 8, "float32", "xla"),
              {"variant": "flattened"})

    def warmup():
        calls.append(len(calls))
        return make_warmup(cache, backend="xla")()

    svc = ShardedTriggerService(
        lambda feeds: {"y": feeds["x"] * 2.0}, n_replicas=2, microbatch=4,
        window_s=1e-3, devices=None, warmup_fn=warmup)
    try:
        # once per distinct device — both replicas share the default
        # device, so the second warm-up would re-execute a hot cache
        assert calls == [0]
        assert svc.replicas[0].warmed == 1
        assert svc.replicas[1].warmed == 0
        fut = svc.submit({"x": np.ones((3,), np.float32)})
        assert fut.result(timeout=30)["y"].sum() == 6.0
    finally:
        svc.close()


def test_replica_engine_survives_failing_warmup():
    """A warm-up that raises fails the service's start instead of
    leaving a lane that fails every batch; the lanes built before it
    are closed (no thread outlives the failed start), and a service
    started without the failing warm-up serves normally."""
    import threading

    import numpy as np

    from repro.serving import ShardedTriggerService

    def bad_warmup():
        raise RuntimeError("stale cache entry")

    def infer(feeds):
        return {"y": feeds["x"] + 1.0}

    before = set(threading.enumerate())
    with pytest.raises(RuntimeError, match="stale cache entry"):
        ShardedTriggerService(
            routes={"a": infer, "b": infer}, n_replicas=1, microbatch=2,
            window_s=1e-3, devices=None,
            warmup_fn={"a": lambda: 1, "b": bad_warmup})
    leaked = [t for t in threading.enumerate()
              if t not in before and t.is_alive()]
    assert leaked == []
    svc = ShardedTriggerService(infer, n_replicas=1, microbatch=2,
                                window_s=1e-3, devices=None)
    try:
        fut = svc.submit({"x": np.zeros((2,), np.float32)})
        assert fut.result(timeout=30)["y"].sum() == 2.0
    finally:
        svc.close()


def test_warm_from_cache_reports_skipped_entries(capsys):
    """Stale entries may be skipped, but never silently."""
    cache = TuningCache()
    cache.put(fused_dense_key(16, 8, 8, "float32", "xla"),
              {"variant": "flattened"})
    stale = KernelKey("fused_dense", (16, 8), "float32", "xla")
    cache.put(stale, {"variant": "flattened"})
    assert warm_from_cache(cache) == 1
    out = capsys.readouterr().out
    assert "skipped 1 stale cache entry" in out and stale.encode() in out
    assert warm_from_cache(cache, kernels=("gravnet",)) == 0
    assert "skipped" not in capsys.readouterr().out


# -------------------------------------------------------- regression gate ----
def _bench(calib, **metrics):
    return {"schema": 1, "backend": "cpu", "calibration_s": calib,
            "metrics": metrics}


def test_regression_compare_passes_within_threshold():
    from benchmarks.regression import compare
    base = _bench(0.01, a_s=0.10, b_s=0.20)
    fresh = _bench(0.01, a_s=0.11, b_s=0.19)
    assert compare(base, fresh, 0.25) == []


def test_regression_compare_fails_on_slowdown():
    from benchmarks.regression import compare
    base = _bench(0.01, a_s=0.10, b_s=0.20)
    fresh = _bench(0.01, a_s=0.26, b_s=0.20)    # 2.6x on metric a
    regs = compare(base, fresh, 0.25)
    assert [r["metric"] for r in regs] == ["a_s"]
    assert regs[0]["slowdown"] == pytest.approx(2.6)


def test_regression_compare_normalizes_by_calibration():
    from benchmarks.regression import compare
    base = _bench(0.01, a_s=0.10)
    # machine is uniformly 2x slower: calibration scales too → no fail
    fresh = _bench(0.02, a_s=0.20)
    assert compare(base, fresh, 0.25) == []
    # metric slowed 2x on the same-speed machine → fail
    fresh2 = _bench(0.01, a_s=0.20)
    assert len(compare(base, fresh2, 0.25)) == 1


def test_regression_compare_flags_missing_metric():
    from benchmarks.regression import compare
    base = _bench(0.01, a_s=0.10, gone_s=0.10)
    fresh = _bench(0.01, a_s=0.10)
    regs = compare(base, fresh, 0.25)
    assert regs == [{"metric": "gone_s", "missing": True}]


def test_regression_check_exit_codes(tmp_path):
    from benchmarks import regression
    base_p = tmp_path / "base.json"
    fresh_p = tmp_path / "fresh.json"
    out_p = tmp_path / "out.json"
    base_p.write_text(json.dumps(_bench(0.01, a_s=0.10)))
    fresh_p.write_text(json.dumps(_bench(0.01, a_s=0.10)))
    ok = regression.main(["--check", "--baseline", str(base_p),
                          "--fresh", str(fresh_p), "--out", str(out_p)])
    assert ok == 0 and out_p.exists()
    bad = regression.main(["--check", "--baseline", str(base_p),
                           "--fresh", str(fresh_p),
                           "--inject-slowdown", "2.0",
                           "--out", str(out_p)])
    assert bad == 1
    missing = regression.main(["--check",
                               "--baseline", str(tmp_path / "none.json"),
                               "--fresh", str(fresh_p)])
    assert missing == 2


def test_committed_baseline_is_loadable():
    from benchmarks.regression import BASELINE_PATH, _load
    base = _load(BASELINE_PATH)
    assert base["metrics"] and base["calibration_s"] > 0


# ---------------------------------------------------------- bench harness ----
def test_run_harness_unknown_section_exits_nonzero(capsys):
    from benchmarks import run as bench_run
    assert bench_run.main(["no_such_section"]) == 2


def test_run_harness_failing_section_exits_nonzero(monkeypatch, capsys):
    import benchmarks.kernels_bench as kb
    from benchmarks import run as bench_run

    def boom():
        raise RuntimeError("section is broken")

    monkeypatch.setattr(kb, "run", boom)
    assert bench_run.main(["kernels"]) == 1
    out = capsys.readouterr().out
    assert "kernels,nan,ERROR" in out


@pytest.mark.parametrize("rows,d_in,d_out", [
    (1024, 32, 7), (1024, 108, 64), (1024, 64, 26), (8192, 4, 64),
    (600, 200, 5), (4096, 1000, 300), (6, 2048, 2048)])
def test_fused_dense_tiles_are_tpu_legal(rows, d_in, d_out):
    """Every fused-dense block (default and searched) keeps its last
    two dims multiples of (8, 128) or equal to the wrapper-padded
    array dim — the TPU lowering refuses anything else."""
    from repro.tuning.candidates import fused_dense_candidates

    def legal(block, dim, align):
        padded = -(-dim // block) * block
        return block % align == 0 or block == padded

    for cfg in fused_dense_candidates(rows, d_in, d_out):
        if cfg["variant"] != "looped":
            continue
        bm, bn, bk = cfg["bm"], cfg["bn"], cfg["bk"]
        assert legal(bm, rows, 8), cfg            # x / out sublanes
        assert legal(bk, d_in, 128), cfg          # x lanes
        assert legal(bk, d_in, 8), cfg            # w sublanes
        assert legal(bn, d_out, 128), cfg         # w / out / bias lanes
