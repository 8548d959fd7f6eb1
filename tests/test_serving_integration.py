"""End-to-end system test: design flow -> compiled pipeline -> real-time
serving engine, on synthetic Belle II events (the paper's demonstrator
in miniature); plus unit coverage for the sharded layer (router
policies, merged in-order release, padded-event accounting)."""
import threading
import time

import numpy as np
import jax
import pytest

from repro.core import caloclusternet as ccn
from repro.core.passes.parallelize import Requirements
from repro.core.pipeline import deploy
from repro.data.belle2 import Belle2Config, generate
from repro.serving import ShardedTriggerService, TriggerServingEngine


def test_trigger_pipeline_through_serving_engine():
    cfg = ccn.CCNConfig(n_hits=32, n_crystals=576)
    gen = Belle2Config(n_crystals=576, grid=(24, 24), n_hits=32,
                       noise_rate=8.0)
    params = ccn.init(jax.random.PRNGKey(0), cfg)
    graph = ccn.to_graph(params, cfg)
    calib = generate(gen, 32, seed=1)
    feeds = {"hits": calib["feats"], "mask": calib["mask"]}
    req = Requirements(design_point=3, platform="cpu",
                       precision_policy="mixed", n_hits=cfg.n_hits,
                       target_throughput=2e4, max_latency_s=2e-3)
    pipe = deploy(graph, req, calibration_feeds=feeds)

    def infer(batch):
        return pipe({"hits": batch["hits"], "mask": batch["mask"]})

    # warm up compile outside the engine
    infer({"hits": calib["feats"][:max(pipe.microbatch, 8)],
           "mask": calib["mask"][:max(pipe.microbatch, 8)]})

    eng = TriggerServingEngine(infer, microbatch=max(pipe.microbatch, 8),
                               window_s=5e-3)
    events = generate(gen, 40, seed=2)
    futs = [eng.submit({"hits": events["feats"][i],
                        "mask": events["mask"][i]}) for i in range(40)]
    results = [f.result(timeout=120) for f in futs]
    eng.drain()
    # in-order, complete, structurally sound
    assert eng.stats.completed == 40
    for r in results:
        assert set(r) >= {"beta", "coords", "energy", "cls", "cps"}
        assert r["cps"]["cluster_xy"].shape == (cfg.k_max, 2)
        assert np.isfinite(np.asarray(r["coords"])).all()
    # engine result i must equal direct pipeline result for event i
    direct = pipe({"hits": events["feats"], "mask": events["mask"]})
    for i in (0, 7, 39):
        np.testing.assert_allclose(
            np.asarray(results[i]["coords"]),
            np.asarray(direct["coords"][i]), rtol=1e-5, atol=1e-5)
    eng.close()


# ------------------------------------------------------- sharded layer ----
def _echo_with_delay(feeds):
    """Identity inference whose service time is carried in the event:
    lets a test force specific replicas to finish out of order."""
    time.sleep(float(np.max(feeds["delay"])))
    return {"y": feeds["x"]}


def test_sharded_inorder_release_under_out_of_order_completion():
    """Replica 0's batches are made much slower than the others', so
    later-submitted events finish computing first — the merged release
    stage must still resolve futures in global submission order."""
    svc = ShardedTriggerService(_echo_with_delay, n_replicas=4,
                                microbatch=4, window_s=2e-3,
                                policy="round_robin", devices=None)
    n = 32
    order, lock = [], threading.Lock()

    def track(i):
        def cb(_fut):
            with lock:
                order.append(i)
        return cb

    futs = []
    for i in range(n):
        # round_robin: event i -> replica i % 4; replica 0 is the slow one
        delay = 0.15 if i % 4 == 0 else 0.01
        fut = svc.submit({"x": np.float32(i), "delay": np.float32(delay)})
        fut.add_done_callback(track(i))
        futs.append(fut)
    results = [f.result(timeout=60) for f in futs]
    svc.drain()
    assert order == sorted(order), "release stage broke submission order"
    for i, r in enumerate(results):
        assert float(r["y"]) == float(i)
    # out-of-order completion actually happened: fast replicas completed
    # batches whose events could not be released until replica 0 caught up
    assert svc.stats.completed == n
    svc.close()


def test_router_round_robin_even_assignment():
    svc = ShardedTriggerService(
        lambda feeds: {"y": feeds["x"]}, n_replicas=3, microbatch=2,
        window_s=2e-3, policy="round_robin", devices=None)
    futs = [svc.submit({"x": np.float32(i)}) for i in range(12)]
    for f in futs:
        f.result(timeout=30)
    svc.drain()
    assert [r.stats.submitted for r in svc.replicas] == [4, 4, 4]
    assert svc.stats.completed == 12
    svc.close()


def test_router_least_loaded_prefers_idle_replica():
    svc = ShardedTriggerService(_echo_with_delay, n_replicas=2,
                                microbatch=1, window_s=1e-3,
                                policy="least_loaded", devices=None)
    slow = svc.submit({"x": np.float32(0), "delay": np.float32(0.3)})
    time.sleep(0.05)  # let the slow event reach replica 0's dispatch
    fast = svc.submit({"x": np.float32(1), "delay": np.float32(0.0)})
    slow.result(timeout=30)
    fast.result(timeout=30)
    svc.drain()
    assert svc.replicas[0].stats.submitted == 1
    assert svc.replicas[1].stats.submitted == 1
    svc.close()


def test_padded_event_accounting():
    eng = TriggerServingEngine(lambda feeds: {"y": feeds["x"]},
                               microbatch=8, window_s=5e-2)
    futs = [eng.submit({"x": np.float32(i)}) for i in range(5)]
    for f in futs:
        f.result(timeout=30)
    eng.drain()
    s = eng.stats
    assert s.completed == 5
    # every launched batch is zero-padded to the micro-batch size; only
    # real events are ever released
    assert s.padded_events == 8 * s.batches - 5
    assert s.summary()["padded_events"] == s.padded_events
    eng.close()


def test_failed_batch_isolates_and_preserves_order():
    """An inference fault fails only that batch's futures; later events
    still release, so one poisoned batch cannot wedge the service."""
    def infer(feeds):
        if np.max(feeds["x"]) < 0:
            raise RuntimeError("poisoned batch")
        return {"y": feeds["x"]}

    svc = ShardedTriggerService(infer, n_replicas=1, microbatch=1,
                                window_s=1e-3, devices=None)
    bad = svc.submit({"x": np.float32(-1)})
    good = svc.submit({"x": np.float32(2)})
    with pytest.raises(RuntimeError, match="poisoned"):
        bad.result(timeout=30)
    assert float(good.result(timeout=30)["y"]) == 2.0
    svc.drain()
    assert svc.replicas[0].stats.failed == 1
    assert svc.stats.completed == 1
    svc.close()


def test_aggregate_stats_report_per_replica_budget():
    svc = ShardedTriggerService(_echo_with_delay, n_replicas=2,
                                microbatch=4, window_s=2e-3,
                                devices=None)
    futs = [svc.submit({"x": np.float32(i), "delay": np.float32(0.005)})
            for i in range(16)]
    for f in futs:
        f.result(timeout=30)
    svc.drain()
    s = svc.stats.summary()
    assert s["replicas"] == 2 and len(s["per_replica"]) == 2
    assert s["completed"] == 16
    bud = s["budget"]
    # the deadline loop's stages, per launch; the echo pipeline sleeps
    # 5 ms in its call
    for k in ("queue_wait_us_mean", "pack_us_mean", "pipeline_us_mean",
              "fetch_us_mean", "release_us_mean"):
        assert bud[k] is not None and bud[k] >= 0.0
    assert bud["pipeline_us_mean"] >= 5000.0
    assert bud["slot_wait_us_mean"] is None   # a streaming-loop stage
    # per-replica budgets carry the same breakdown
    for rs in s["per_replica"]:
        assert rs["budget"]["pipeline_us_mean"] >= 5000.0
    svc.close()


def test_sharded_service_matches_direct_pipeline():
    """Two virtual replicas sharing one deployed executable produce, in
    submission order, exactly the per-event results of a direct batched
    pipeline call."""
    cfg = ccn.CCNConfig(n_hits=32, n_crystals=576)
    gen = Belle2Config(n_crystals=576, grid=(24, 24), n_hits=32,
                       noise_rate=8.0)
    params = ccn.init(jax.random.PRNGKey(1), cfg)
    graph = ccn.to_graph(params, cfg)
    calib = generate(gen, 32, seed=4)
    req = Requirements(design_point=3, platform="cpu",
                       precision_policy="fp", n_hits=cfg.n_hits,
                       target_throughput=2e4, max_latency_s=2e-3)
    pipe = deploy(graph, req)

    def infer(batch):
        return pipe({"hits": batch["hits"], "mask": batch["mask"]})

    mb = max(pipe.microbatch, 8)
    infer({"hits": calib["feats"][:mb], "mask": calib["mask"][:mb]})
    svc = ShardedTriggerService(infer, n_replicas=2, microbatch=mb,
                                window_s=5e-3, devices=None)
    events = generate(gen, 24, seed=6)
    futs = [svc.submit({"hits": events["feats"][i],
                        "mask": events["mask"][i]}) for i in range(24)]
    results = [f.result(timeout=120) for f in futs]
    svc.drain()
    direct = pipe({"hits": events["feats"], "mask": events["mask"]})
    for i in range(24):
        np.testing.assert_allclose(
            np.asarray(results[i]["coords"]),
            np.asarray(direct["coords"][i]), rtol=1e-5, atol=1e-5)
    assert svc.stats.completed == 24
    assert sum(r.stats.submitted for r in svc.replicas) == 24
    svc.close()


def test_deployed_pipeline_matches_functional_trigger_decisions():
    """fp-precision deployed pipeline == functional model, bit-for-bit
    trigger decisions (the paper's sw/emu/hw agreement analogue)."""
    cfg = ccn.CCNConfig(n_hits=32, n_crystals=576)
    gen = Belle2Config(n_crystals=576, grid=(24, 24), n_hits=32,
                       noise_rate=8.0)
    params = ccn.init(jax.random.PRNGKey(3), cfg)
    graph = ccn.to_graph(params, cfg)
    events = generate(gen, 24, seed=5)
    feeds = {"hits": events["feats"], "mask": events["mask"]}
    req = Requirements(design_point=2, platform="cpu",
                       precision_policy="fp", n_hits=cfg.n_hits,
                       target_throughput=1e4, max_latency_s=2e-3)
    out = deploy(graph, req)(feeds)
    ref = ccn.apply(params, feeds["hits"], feeds["mask"], cfg)
    cps_ref = ccn.cps(ref, feeds["mask"], cfg)
    np.testing.assert_array_equal(np.asarray(out["cps"]["trigger"]),
                                  np.asarray(cps_ref["trigger"]))
    np.testing.assert_array_equal(np.asarray(out["cps"]["n_clusters"]),
                                  np.asarray(cps_ref["n_clusters"]))
