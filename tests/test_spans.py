"""The server's own trace: stage spans with their stats and counters
(``repro/spans.py``), the lane stages on the threads that run them,
the pipelines' spans, the latency budget built from the stage
counters, and latency taken at release."""
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.serving import ShardedTriggerService


def _profiled(tmp_path, fn):
    """Run ``fn`` under a CPU profiler session; its ``trigger.*``
    spans as ``(name, thread line, stats)``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path[0])
    return [(e.name, f"{p.name}#{i}", {k: v for k, v in e.stats})
            for p in pd.planes if p.name.startswith("/host:")
            for i, line in enumerate(p.lines) for e in line.events
            if e.name.startswith("trigger.")]


def test_stage_emits_its_span_and_counts(tmp_path):
    c = spans.StageCounters()

    def work():
        with spans.stage(spans.PACK, c, events=16) as st:
            time.sleep(2e-3)
            st.set(queued=3)

    got = _profiled(tmp_path, work)
    assert [(n, s) for n, _, s in got] == [
        (spans.PACK, {"events": 16, "queued": 3})]
    with spans.stage(spans.PACK, c, events=1):   # no profiler running
        pass
    snap = c.snapshot()
    assert list(snap) == [spans.PACK]
    assert snap[spans.PACK]["count"] == 2
    assert snap[spans.PACK]["max_s"] >= 2e-3
    assert snap[spans.PACK]["total_s"] >= snap[spans.PACK]["max_s"]


def test_stage_counters_under_contention():
    """Stages of one lane run on several threads: no update is lost."""
    import sys
    import threading
    c = spans.StageCounters()
    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def hammer():
            for _ in range(2000):
                c.add(spans.PIPELINE, 1e-6)
        ts = [threading.Thread(target=hammer) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(prev)
    assert c.snapshot()[spans.PIPELINE]["count"] == 16000


def _infer(feeds):
    return {"y": jnp.asarray(feeds["x"]) * 2.0}


@pytest.mark.parametrize("loop", ["streaming", "deadline"])
def test_lane_stages_on_their_threads(tmp_path, loop):
    """The streaming loop's launcher, dispatch worker and harvester
    each record their own stages; the deadline loop records pack,
    pipeline, fetch and release on its dispatch worker."""
    svc = ShardedTriggerService(_infer, microbatch=4, devices=None,
                                loop=loop)

    def serve():
        futs = [svc.submit({"x": np.float32(i)}) for i in range(24)]
        for f in futs:
            f.result(timeout=30)
        svc.drain()

    got = _profiled(tmp_path, serve)
    svc.close()
    line = {}
    for name, ln, _ in got:
        line.setdefault(name, set()).add(ln)
    if loop == "streaming":
        assert set(line) == set(spans.LANE_STAGES)
        launcher, = line[spans.PACK]
        harvester, = line[spans.RELEASE]
        assert line[spans.SLOT_WAIT] == {launcher}
        assert line[spans.FETCH] == line[spans.RESULT_WAIT] == {harvester}
        assert launcher != harvester
        assert not line[spans.PIPELINE] & {launcher, harvester}
    else:
        assert set(line) == {spans.PACK, spans.PIPELINE, spans.FETCH,
                             spans.RELEASE}
    released = sum(s["events"] for n, _, s in got if n == spans.RELEASE)
    assert released == 24
    assert all("held" in s for n, _, s in got if n == spans.RELEASE)
    snap = svc.replicas[0].stats.stages.snapshot()
    assert snap[spans.RELEASE]["count"] == svc.stats.batches
    bud = svc.stats.summary()["budget"]
    assert bud["pipeline_us_mean"] > 0
    assert (bud["slot_wait_us_mean"] is None) == (loop == "deadline")


def test_latency_runs_to_release():
    """An event finished early but held behind a slower earlier one
    is timed to its release, not to its own completion."""
    def infer(feeds):
        time.sleep(float(np.max(feeds["delay"])))
        return {"y": feeds["x"]}

    svc = ShardedTriggerService(infer, n_replicas=2, microbatch=1,
                                devices=None, loop="streaming")
    slow = svc.submit({"x": np.float32(0), "delay": np.float32(0.2)})
    fast = svc.submit({"x": np.float32(1), "delay": np.float32(0.0)})
    fast.result(timeout=30)
    assert slow.done()
    svc.drain()
    fast_lat = svc.replicas[1].stats.samples("latencies_s")
    assert fast_lat and fast_lat[0] >= 0.15
    svc.close()


def _ccn():
    import repro.core.caloclusternet as ccn
    from repro.core.pipeline import Requirements, deploy
    from repro.data.belle2 import generate
    cfg = ccn.current_detector_config()
    g = ccn.to_graph(ccn.init(jax.random.PRNGKey(1), cfg), cfg)
    req = Requirements(design_point=3, platform="cpu",
                       precision_policy="fp", n_hits=cfg.n_hits,
                       target_throughput=5e4, max_latency_s=2e-3)
    from repro.launch.serve import _ccn_configs
    data = generate(_ccn_configs("current")[1], 12, seed=3)
    return g, req, deploy, {"hits": data["feats"], "mask": data["mask"]}


def test_pipeline_spans(tmp_path):
    """Per-event chunks, padding and concatenation; the ragged path's
    pack (with its bin fill), launch, fetch and unpack."""
    g, req, deploy, feeds = _ccn()
    pipe = deploy(g, req)
    ragged = deploy(g, req, batch=2, ragged=True)
    mb = pipe.microbatch
    odd = {k: v[:mb + 1] for k, v in feeds.items()}
    pipe(odd)
    ragged(feeds)
    got = _profiled(tmp_path / "per_event", lambda: pipe(odd))
    assert [(n, s) for n, _, s in got] == [
        (spans.PIPELINE_PAD, {}), (spans.PIPELINE_CHUNK, {"rows": mb}),
        (spans.PIPELINE_CHUNK, {"rows": mb}), (spans.PIPELINE_CONCAT, {})]
    # the packed executable is a CompiledPipeline: its chunk and concat
    # spans nest inside each ragged launch
    got = _profiled(tmp_path / "ragged", lambda: ragged(feeds))
    names = [n for n, _, _ in got]
    pack, = [s for n, _, s in got if n == spans.RAGGED_PACK]
    counts = feeds["mask"].sum(1)
    assert pack["events"] == len(counts)
    assert pack["rows"] == int(counts.sum())
    assert pack["bins"] % ragged.batch == 0
    launches = pack["bins"] // ragged.batch
    for name in (spans.RAGGED_LAUNCH, spans.RAGGED_FETCH):
        assert names.count(name) == launches
    assert names.count(spans.RAGGED_UNPACK) >= launches


def test_edge_model_pack_counts_its_edges(tmp_path):
    """The streaming launcher's pack stage of an edge model (event feeds
    that hold an ``edge_mask``) also states the real edges it stages,
    counted on the host."""
    rng = np.random.default_rng(0)
    evs = [{"x": np.float32(i),
            "edge_mask": (rng.random(16) < 0.5).astype(np.float32)}
           for i in range(24)]
    svc = ShardedTriggerService(_infer, microbatch=4, devices=None,
                                loop="streaming")

    def serve():
        futs = [svc.submit(ev) for ev in evs]
        for f in futs:
            f.result(timeout=30)
        svc.drain()

    got = _profiled(tmp_path, serve)
    svc.close()
    packs = [st for n, _, st in got if n == spans.PACK]
    assert sum(st["events"] for st in packs) == len(evs)
    assert sum(st["edges"] for st in packs) == \
        sum(int(ev["edge_mask"].sum()) for ev in evs)


def test_graph_ops_lower_under_their_scopes():
    """Each graph op's name reaches its device operations' op_name."""
    g, req, deploy, feeds = _ccn()
    pipe = deploy(g, req)
    mb = pipe.microbatch
    text = pipe.lower({k: v[:mb] for k, v in feeds.items()}
                      ).compile().as_text()
    assert 'op_name="jit(whole)/cps/' in text
    assert 'op_name="jit(whole)/gn0_agg.block/' in text
