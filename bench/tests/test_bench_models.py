"""The model seam: a configuration of a model other than CaloClusterNet
becomes a runnable cell from new files alone (its configuration and
its reference, dropped into their directories, and its deployment in
``serve.py``), and a fault planted in it reads not correct; the CCN
reference's seam makes the same events, weights and compared numbers
as the harness made before it went through the seam."""
import filecmp
import json
import sys

import jax
import numpy as np
import pytest

from bench import events, run, sut
from bench.tests import harness

GRAPH = ("test-graphsage", "test-backlog")


@pytest.fixture(autouse=True)
def _own_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def _graphsage_deployment(args, params):
    """``serve.py``'s GraphSAGE route at its registry widths, taking
    the weights it is given: the form of ``serve.ccn_deployment``."""
    from repro.core.graph_ir import export_graph
    from repro.launch import serve
    from repro.models.gnn import graphsage
    cfg = graphsage.GraphSAGEConfig(n_layers=2, d_hidden=32, d_in=16,
                                    n_classes=5)
    return {"graph": export_graph("graphsage", params, cfg),
            "req": serve._requirements(args, precision_policy="fp",
                                       n_hits=serve._EDGE_N)}


@pytest.fixture
def graphsage_served(monkeypatch):
    """``serve.graphsage_deployment``: ``serve.py``'s own where it has
    one, else the route above in its place."""
    if str(run.ROOT / "src") not in sys.path:
        sys.path.insert(0, str(run.ROOT / "src"))
    from repro.launch import serve
    if not hasattr(serve, "graphsage_deployment"):
        monkeypatch.setattr(serve, "graphsage_deployment",
                            _graphsage_deployment, raising=False)


def _bench_files(root):
    return sorted(p.relative_to(root) for p in (root / "bench").rglob("*")
                  if p.is_file() and "__pycache__" not in p.parts)


def test_second_model_runs_from_new_files(tmp_path, graphsage_served):
    root = harness.checkout(tmp_path, [GRAPH])
    dropped = {f"bench/{sub}/{f.name}" for sub in
               ("configs", "traffic", "metrics", "references")
               for f in (harness.DATA / sub).glob("*.*")}
    for rel in _bench_files(root):
        if str(rel) not in dropped:    # the harness's own files, unedited
            assert filecmp.cmp(root / rel, harness.BENCH.parent / rel,
                               shallow=False), rel
    out = run.run_cell(".".join(GRAPH), 41, 0.5, False, root=root,
                       require_chip=False)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    # no decision in this model: the comparison has no decision_miss
    assert list(out["compared"]) == ["head_gap", "hits_off",
                                     "order_breaks", "unanswered"]


def _first_event_moved(fn, params):
    """The logits of the first event of every launch moved by one."""
    def call(feeds):
        out = jax.device_get(fn(feeds))
        out = {k: np.array(v) for k, v in out.items()}
        out["logits"][0] += 1.0
        return out
    return call


def test_second_model_fault_is_not_correct(tmp_path, graphsage_served):
    root = harness.checkout(tmp_path, [GRAPH])
    out = run.run_cell(".".join(GRAPH), 42, 0.5, False, root=root,
                       require_chip=False, fault=_first_event_moved)
    assert out is not None
    assert not out["correct"], out["compared"]
    assert out["compared"]["hits_off"]["value"] > 0


def test_a_cell_serves_one_model():
    from repro.launch import serve
    args = serve.build_parser().parse_args(["--model", "ccn", "graphsage"])
    with pytest.raises(ValueError, match="one model"):
        sut.deployment(args, None)


# ------------------------------------------------------ the CCN seam ----
CCN_CFG = json.loads((harness.DATA / "configs" /
                      "test-current-fp.json").read_text())
DET = {**CCN_CFG["detector"], "noise_rate": 8.0}


def _ccn():
    return run.reference(CCN_CFG)


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_ccn_pool_and_weights_are_the_old_setup(seed):
    ccn = _ccn()
    w, gain = CCN_CFG["widths"], CCN_CFG["weight_gain"]
    pool = ccn.pool(DET, 16, seed)
    ev = events.generate(DET, 16, seed)
    for part in ("feeds", "data"):
        assert pool[part].keys() == ev.keys()
        for k in ev:
            np.testing.assert_array_equal(pool[part][k], ev[k])
            assert pool[part][k].dtype == ev[k].dtype
    np.testing.assert_array_equal(pool["rows"], ev["mask"].sum(1))
    calib = ccn.pool(DET, 64, seed + 1)
    got = ccn.weights(seed, w, gain, calib)
    old = events.generate(DET, 64, seed + 1)
    want = ccn.place_threshold(ccn.init_params(seed, w, gain), old["hits"],
                               old["mask"], w)
    leaves = jax.tree_util.tree_leaves_with_path(want)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for (path, a), b in zip(leaves, jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a),
                                      err_msg=str(path))


def test_ccn_model_flops_is_the_old_count():
    from bench import flops
    w = CCN_CFG["widths"]
    assert [_ccn().model_flops(n, w) for n in (1, 17, 32)] == \
        [flops.model_flops(n, w) for n in (1, 17, 32)]


def _synthetic(ccn, pool, params, w):
    """A window of 8 events, each submitted once: event 3 failed, 3 and
    4 released out of order; the served outputs are the reference's,
    with event 0's beta moved by a quarter of the head's scale on every
    hit, two real hits of event 1 moved by three times the coords
    scale, and event 2's decision flipped."""
    heads, sel = ccn.forward(params, pool["hits"], pool["mask"], w)
    real = pool["mask"] > 0
    real[3] = False             # the scales are over the sampled events
    keep = np.ones(8, bool)
    keep[3] = False
    win = run.Window(8, keep)
    win.n = 8
    win.pool_idx[:] = np.arange(8)
    win.order = [0, 1, 2, 4, 3, 5, 6, 7]
    win.failed[3] = True
    for e in range(8):
        if e == 3:
            continue
        out = {h: np.array(heads[h][e]) for h in heads}
        out["cps"] = {"trigger": np.asarray(sel["trigger"][e])}
        if e == 0:
            sc = np.sqrt(np.mean(heads["beta"][real] ** 2))
            out["beta"] = out["beta"] + np.float32(0.25 * sc)
        if e == 1:
            sc = np.sqrt(np.mean(heads["coords"][real] ** 2))
            out["coords"][np.flatnonzero(real[1])[:2]] += np.float32(3 * sc)
        if e == 2:
            out["cps"]["trigger"] = ~out["cps"]["trigger"]
        win.kept[e] = out
    return win


def test_judge_compares_as_the_ccn_only_path():
    """The numbers the CCN-only comparison gave on this window (read
    with it before the seam), written out."""
    from bench import check
    ccn = _ccn()
    w = CCN_CFG["widths"]
    pool = ccn.pool(DET, 8, 5)
    params = ccn.weights(5, w, CCN_CFG["weight_gain"],
                         ccn.pool(DET, 64, 6))
    win = _synthetic(ccn, pool["data"], params, w)
    v = check.judge(CCN_CFG, ccn, params, pool, win, 5)
    got = {k: c["value"] for k, c in v["compared"].items()}
    assert list(got) == ["head_gap", "hits_off", "decision_miss",
                         "order_breaks", "unanswered"]
    assert got == {"head_gap": pytest.approx(0.25, rel=1e-5),
                   "hits_off": HITS_OFF, "decision_miss": 1 / 7,
                   "order_breaks": 2, "unanswered": 1}
    assert {k: c["limit"] for k, c in v["compared"].items()} == {
        "head_gap": 2e-05, "hits_off": 0, "decision_miss": 0,
        "order_breaks": 0, "unanswered": 0}
    assert (v["sampled"], v["tie_events"]) == (7, TIE_EVENTS)
    assert v["correct"] is False


# event 0's 32 real hits and event 1's two; no event of the sample is
# near a k-NN tie (its smallest margin is 1.26e-4 against 1e-5)
HITS_OFF = 34
TIE_EVENTS = 0
