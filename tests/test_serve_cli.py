"""The serving driver as a library call: ``serve.main(argv)`` returns
its summary, names the device it ran on, exits non-zero when an event
fails without injected faults, and places the compilation cache only
where it is told to."""
import jax
import numpy as np
import pytest

from repro.core.pipeline import CompiledPipeline, deploy
from repro.launch import serve

_SMALL = ["--detector", "current", "--precision", "fp", "--events", "24",
          "--train-steps", "0"]
_configure_compile_cache = serve.configure_compile_cache


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    """serve.main turns JAX's persistent cache on for its process; a
    test worker must not carry that into every later test."""
    monkeypatch.setattr(serve, "configure_compile_cache", lambda: None)


def test_main_returns_summary_with_device_and_outputs(capsys):
    s = serve.main(_SMALL)
    assert s["released"] == 24 and s["failed"] == 0 and s["in_order"]
    assert s["device"]["platform"] == "cpu" and s["device"]["count"] >= 1
    assert s["backend"] == "xla"            # a CPU deploys the reference
    assert s["per_replica"] == [{"replica_id": 0, "completed": 24,
                                 "devices": [str(jax.devices()[0])]}]
    assert len(s["outputs"]) == 24
    assert all("cps" in o for o in s["outputs"])
    # the served outputs are the deployed pipeline's own, in order
    args = serve.build_parser().parse_args(_SMALL)
    m = serve.ccn_deployment(args, serve.ccn_params(args))
    ref = deploy(m["graph"], m["req"],
                 calibration_feeds=m["calibration_feeds"])
    evs = m["events"](24, 7)                # serve.main's own events
    want = ref({k: np.stack([e[k] for e in evs]) for k in ("hits",
                                                           "mask")})
    got = np.stack([o["cps"]["trigger"] for o in s["outputs"]])
    np.testing.assert_array_equal(got, np.asarray(want["cps"]["trigger"]))
    out = capsys.readouterr().out
    assert "(cpu, 1 replica(s)" in out and "CPU" not in out


def _fail_after_warmup(monkeypatch):
    """Every pipeline call after the driver's warm-up call raises."""
    real = CompiledPipeline.__call__
    calls = []

    def flaky(self, feeds):
        calls.append(1)
        if len(calls) > 1:
            raise RuntimeError("device lost")
        return real(self, feeds)
    monkeypatch.setattr(CompiledPipeline, "__call__", flaky)


def test_failed_events_exit_nonzero_without_fault_plan(monkeypatch):
    _fail_after_warmup(monkeypatch)
    with pytest.raises(SystemExit, match="24 event"):
        serve.main(_SMALL)


def test_release_out_of_order_exits_nonzero():
    chaos = {"faults": object()}             # even under a fault plan
    serve._fail_unless_sound(chaos, 3, in_order=True)
    with pytest.raises(SystemExit, match="submission order"):
        serve._fail_unless_sound(chaos, 0, in_order=False)


def test_failed_events_exit_nonzero_on_multimodel_path(monkeypatch):
    _fail_after_warmup(monkeypatch)
    with pytest.raises(SystemExit):
        serve.main(["--model", "graphsage", "--events", "8",
                    "--train-steps", "0"])


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_goes_where_told(monkeypatch, env_dir):
    updates = {}
    monkeypatch.setattr(serve.jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = _configure_compile_cache()
        assert path == str(serve.REPO_ROOT / ".jax_cache")
        assert updates["jax_compilation_cache_dir"] == path
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert _configure_compile_cache() == env_dir
        assert updates == {}                 # JAX owns it: set nothing
