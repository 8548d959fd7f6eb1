"""The reduction of the program's own spans and op scopes
(``bench/stages.py``): scopes read from ``op_name`` paths, self time
of nested device operations, span totals, idle gaps labelled by
program span, on synthetic traces with hand-made numbers, on a slice
recorded on a TPU v5e, and in an end-to-end traced run of each
test-size cell on the CPU."""
import json
import pathlib

import pytest

from bench import stages, trace
from bench.tests import harness

MS = 1e6    # ns
TPU0 = "/device:TPU:0"


@pytest.mark.parametrize("path,scope", [
    ("jit(whole)/cps/vmap()/while/body/closed_call/add", "cps"),
    ("jit(whole)/gn0_agg.block/jit(gravnet_block_batched)",
     "gn0_agg.block"),
    ("jit(whole)/while/body/closed_call/enc1+relu/jit(fused_dense)/max",
     "enc1+relu"),
    ("jit(whole)/head_beta.view/slice", "head_beta.view"),
    ("jit(whole)/while", stages.UNSCOPED),
    ("jit(whole)/while/body/add", stages.UNSCOPED),
    ("vmap()/reduce_max", stages.UNSCOPED),
    ("", stages.UNSCOPED),
])
def test_scope_of_an_op_name(path, scope):
    assert stages.scope_of(path) == scope


def _nested():
    # one chip; window [0, 20 ms). A CPS loop [1, 11) holds two body
    # fusions [2, 4) and [5, 8), the second holding a copy [6, 7); a
    # kernel [12, 14) stands alone; a chunk loop's own op [15, 16) has
    # no scope.
    ops = [[1 * MS, 10 * MS, "while.3"], [2 * MS, 2 * MS, "fusion.1"],
           [5 * MS, 3 * MS, "fusion.2"], [6 * MS, 1 * MS, "copy.4"],
           [12 * MS, 2 * MS, "gravnet_block_batched"],
           [15 * MS, 1 * MS, "while.9"]]
    return {"devices": {TPU0: ops},
            "scopes": {TPU0: ["cps", "cps", "cps", "cps",
                              "gn0_agg.block", stages.UNSCOPED]},
            "host": [[0, 20 * MS, "window"], [3 * MS, 12 * MS, "submit"]],
            "program": [
                [0.5 * MS, 0.4 * MS, "trigger.pack", "t1",
                 {"events": 16, "queued": 40}],
                [0.8 * MS, 0.3 * MS, "trigger.pipeline", "t2",
                 {"events": 16}],
                [11 * MS, 1.5 * MS, "trigger.fetch", "t3", {}],
                [14 * MS, 0.8 * MS, "trigger.release", "t3",
                 {"events": 16, "held": 0}],
                [16.5 * MS, 2 * MS, "trigger.release", "t3",
                 {"events": 5, "held": 2}]]}


def test_self_time_subtracts_directly_nested_ops():
    ops = _nested()["devices"][TPU0]
    assert stages.self_times(ops) == pytest.approx(
        [5 * MS, 2 * MS, 2 * MS, 1 * MS, 2 * MS, 1 * MS])


def test_scope_self_times_add_up_to_busy():
    tr = _nested()
    by = dict(stages.device_scopes(tr, 0, 20 * MS))
    assert by == {"cps": pytest.approx(10e-3),
                  "gn0_agg.block": pytest.approx(2e-3),
                  stages.UNSCOPED: pytest.approx(1e-3)}
    busy = trace.device_busy(tr, 0, 20 * MS)[TPU0]
    assert sum(by.values()) == pytest.approx(busy)
    # the HLO stems count the loop's body twice
    assert sum(v for _, v in trace.top_ops(tr, 0, 20 * MS)) > busy
    assert stages.scope_self_time(tr, "cps", 0, 20 * MS) \
        == pytest.approx(10e-3)
    assert stages.scope_self_time(tr, "cps", 12 * MS, 20 * MS) == 0.0


def test_span_time_and_stats():
    tr = _nested()
    assert stages.span_time(tr, "trigger.release", 0, 20 * MS) \
        == (pytest.approx(2.8e-3), 2)
    assert stages.span_time(tr, ["trigger.fetch", "trigger.release"],
                            0, 15 * MS) == (pytest.approx(2.3e-3), 2)
    assert stages.span_stat(tr, "trigger.release", "events",
                            0, 20 * MS) == 21
    assert stages.threads(tr) == {
        "t1": {"trigger.pack"}, "t2": {"trigger.pipeline"},
        "t3": {"trigger.fetch", "trigger.release"}}


def test_idle_gaps_take_the_program_span_first():
    tr = _nested()
    gaps = dict(stages.idle_gaps(tr, 0, 20 * MS))
    # idle [0,1) mid 0.5: pack (the pipeline span starts later, at 0.8)
    # idle [11,12) mid 11.5: fetch, though `submit` started later
    # idle [14,15) mid 14.5: release
    # idle [16,20) mid 18: release; no program span would leave the
    # benchmark's window
    assert gaps == {"trigger.pack": pytest.approx(1e-3),
                    "trigger.fetch": pytest.approx(1e-3),
                    "trigger.release": pytest.approx(5e-3)}
    tr["program"] = []
    assert dict(stages.idle_gaps(tr, 0, 20 * MS)) \
        == dict(trace.idle_gaps(tr, 0, 20 * MS))


def test_summary_reads_the_candidate_metrics():
    s = stages.summary(_nested(), 0, 20 * MS)
    assert s["busy_s"] == pytest.approx(13e-3)
    assert s["scope_sum_over_busy"] == pytest.approx(1.0)
    assert s["cps_device_share_pct"] == pytest.approx(100 * 10 / 13)
    assert s["idle_labelled_by_program_pct"] == pytest.approx(100.0)
    assert s["released_in_window"] == 21
    # fetch + release per pipeline call
    assert s["harvest_host_us"] == pytest.approx(4300.0)
    assert s["ragged_host_us"] is None


DATA = pathlib.Path(__file__).parent / "data"


@pytest.mark.parametrize("path",
                         sorted(DATA.glob("trace_slice_*_spans.json")),
                         ids=lambda p: p.stem)
def test_recorded_slice_with_program_spans(path):
    """A slice of a trace recorded on one TPU v5e with the program's
    spans and op scopes: scope self times add up to the busy time and
    the lane stages sit on three threads."""
    tr = json.loads(path.read_text())
    ops = [o for p in tr["devices"].values() for o in p]
    t0 = min(o[0] for o in ops)
    t1 = max(o[0] + o[1] for o in ops)
    busy = sum(trace.device_busy(tr, t0, t1).values())
    by = dict(stages.device_scopes(tr, t0, t1, n=None))
    assert sum(by.values()) == pytest.approx(busy, rel=0.05)
    assert set(by) - {stages.UNSCOPED}, "no op carries a scope"
    # the launcher, a dispatch worker and the harvester, each on a
    # thread of its own
    roles = {"launcher": {"trigger.slot_wait", "trigger.pack"},
             "dispatch": {"trigger.pipeline"},
             "harvester": {"trigger.result_wait", "trigger.device_wait",
                           "trigger.fetch", "trigger.release"}}
    th = stages.threads(tr)
    on = {role: {t for t, v in th.items() if v & names}
          for role, names in roles.items()}
    assert all(on.values()), on
    assert not (on["launcher"] & on["dispatch"]
                or on["dispatch"] & on["harvester"]
                or on["launcher"] & on["harvester"])


@pytest.fixture
def _own_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


@pytest.mark.parametrize("config", ["test-current-fp",
                                    "test-current-fp-ragged",
                                    "test-current-fp-bucketed"])
def test_traced_cell_holds_spans_on_every_lane_thread(tmp_path, _own_cache,
                                                      config):
    """A ``--trace 1`` run of each test-size cell on the CPU: the
    launcher, a dispatch worker and the harvester each open their
    ``trigger.*`` spans, and the lane counters cover the window."""
    cell = (config, "test-backlog")
    root = harness.checkout(tmp_path, [cell])
    res = stages.run(".".join(cell), 5, 0.5, True, root=root,
                     require_chip=False)
    assert res["correct"]
    prog = res["program"]
    th = prog["threads"]

    def on(name):
        return {t for t, v in th.items() if name in v}

    launcher, dispatch, harvester = (on("trigger.pack"),
                                     on("trigger.pipeline"),
                                     on("trigger.release"))
    assert launcher and dispatch and harvester
    assert not (launcher & dispatch or dispatch & harvester
                or launcher & harvester)
    assert on("trigger.fetch") <= harvester
    assert prog["released_in_window"] > 0
    assert prog["harvest_host_us"] > 0
    assert (prog["ragged_host_us"] is not None) == ("ragged" in config)
    for lane in res["lanes"]:       # one lane per bucket when bucketed
        assert lane["stages"]["trigger.release"]["count"] > 0
        assert lane["largest_max"][1] > 0


def test_helper_cost_is_measured():
    cost = stages.helper_cost(2000)
    assert cost["lane_launch_us"] > 0 and cost["span_us"] > 0


def test_scopes_from_compiled_hlo(tmp_path):
    """Where the trace's device events carry no ``op_name``, the
    compiled module's text gives each instruction's scope; a dump
    directory gives them for every module in it."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("cps"):
            y = jax.lax.fori_loop(0, 3, lambda i, c: c * 2.0 + 1.0, x)
        with jax.named_scope("gn0_agg.block"):
            return jnp.tanh(y) @ y.T

    text = jax.jit(f).lower(jnp.ones((8, 8))).compile().as_text()
    module, by = stages.hlo_scopes(text)
    assert module == "jit_f"
    assert {"cps", "gn0_agg.block"} <= set(by.values())
    assert any(k.startswith("while") and v == "cps" for k, v in by.items())
    (tmp_path / "module_0007.jit_f.after_optimizations.txt").write_text(text)
    (tmp_path / "module_0007.jit_f.before_optimizations.txt").write_text("")
    assert stages.hlo_dump_scopes(tmp_path) == {"jit_f": by}


HLO = """HloModule jit_whole, entry_computation_layout={()->f32[8]}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %mul.3 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(whole)/gn0_agg.block/mul"}
}

%body.2 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %and_reduce_fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1
  ROOT %tuple.4 = (s32[], f32[8]{0}) tuple(%p, %and_reduce_fusion.5)
}

ENTRY %main.9 () -> f32[8] {
  %fusion.12 = f32[8]{0} fusion(), kind=kLoop, calls=%fused_computation.1
  %while.3 = (s32[], f32[8]{0}) while(%fusion.12), condition=%cond.1, body=%body.2, metadata={op_name="jit(whole)/cps/vmap()/while"}
  ROOT %copy.7 = f32[8]{0} copy(%fusion.12)
}
"""


def test_scopes_reach_instructions_without_metadata():
    """A fusion XLA made without ``op_name`` takes its fused
    computation's scope; a loop body's instructions take the loop's;
    an instruction the graph never named stays unscoped."""
    module, by = stages.hlo_scopes(HLO)
    assert module == "jit_whole"
    assert by["fusion.12"] == "gn0_agg.block"
    assert by["while.3"] == "cps"
    assert by["tuple.4"] == "cps"
    # its own fused computation outvotes the loop around it
    assert by["and_reduce_fusion.5"] == "gn0_agg.block"
    assert "copy.7" not in by


def test_module_of_a_time():
    mods = [[0.0, 5.0, "jit_whole"], [6.0, 7.0, "jit_concatenate"]]
    assert stages._module_of(mods, 2.0) == "jit_whole"
    assert stages._module_of(mods, 6.5) == "jit_concatenate"
    assert stages._module_of(mods, 5.5) is None
    assert stages._module_of(mods, -1.0) is None
