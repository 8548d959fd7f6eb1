"""The GatedGCN cell at a test size on the CPU: served through
``serve.gatedgcn_deployment`` and ``deploy()``, compared with
``references/gatedgcn.py``, it reads correct; each planted fault
(training-mode batch-norm, the gate without its Σσ normalisation,
matmuls in three bfloat16 products) reads not correct; and the
reference counts the model's operations as written out by hand."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run

CFG = json.loads((run.ROOT / "bench" / "configs" /
                  "gatedgcn-upgrade-fp.json").read_text())
MIX = json.loads((run.ROOT / "bench" / "tests" / "data" / "traffic" /
                  "test-backlog.json").read_text())
# the configuration at a test size: 2 layers of 12 on the current
# detector's 32-hit events with 4 in-edges per hit. Its head_gap limit
# is set from CPU readings at this size over seeds 1-8: program
# 2.4e-8 to 2.9e-7, control 3.8e-6 to 2.1e-5.
SMALL = {"n_layers": 2, "d_hidden": 12, "d_in": 4, "d_edge_in": 1,
         "n_classes": 3, "readout_layers": 2, "in_edges": 4}
HEAD_GAP = 1e-6
NAME = "gatedgcn-test.test-backlog"


def _cell():
    args = list(CFG["serve_args"])
    args[args.index("--detector") + 1] = "current"
    cfg = {**CFG, "widths": SMALL, "serve_args": args,
           "detector": {"n_crystals": 576, "grid": [24, 24], "n_hits": 32,
                        "in_edges": SMALL["in_edges"]},
           "check": {**CFG["check"], "limits": {"head_gap": HEAD_GAP}}}
    return {"workload": {"name": NAME, "config": "gatedgcn-test",
                         "traffic": "test-backlog", "chips": 1},
            "config": cfg, "traffic": MIX, "end_to_end": [],
            "per_layer": [], "root": run.ROOT}


@pytest.fixture(autouse=True)
def _small_program(tmp_path, monkeypatch):
    """The program's GatedGCN route at the test widths."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    from repro.launch import serve
    from repro.models.gnn import gatedgcn
    monkeypatch.setattr(serve, "_gatedgcn_config",
                        lambda: gatedgcn.GatedGCNConfig(**SMALL))


def _run(seed, **kw):
    out = run.run_cell(NAME, seed, 0.5, False, cell=_cell(),
                       require_chip=False, **kw)
    assert out is not None and out["attempted"] > 0
    return out


def test_cell_is_correct():
    assert CFG["serve_args"][:2] == ["--model", "gatedgcn"]
    out = _run(51)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0
    assert list(out["compared"]) == ["head_gap", "hits_off",
                                     "order_breaks", "unanswered"]


def _train_mode_bn(self, op, vals):
    """The batch-norm normalised by each event's own masked batch
    statistics, as in training."""
    x, mask = vals
    xf = x[..., :op.out_dim]
    m = mask[..., None]
    n = jnp.maximum(m.sum(axis=1, keepdims=True), 1.0)
    mu = (xf * m).sum(axis=1, keepdims=True) / n
    var = (((xf - mu) ** 2) * m).sum(axis=1, keepdims=True) / n
    return ((xf - mu) * jax.lax.rsqrt(var + op.attrs["eps"])
            * op.params["gamma"] + op.params["beta"]) * m


def _plant_in_executor(monkeypatch, fault):
    from repro.core import pipeline
    if fault == "train_bn":
        monkeypatch.setattr(pipeline._Executor, "_batchnorm", _train_mode_bn)
    else:       # the gate's sum without its division by Σσ + ε
        real = pipeline._Executor._eltwise

        def eltwise(self, op, vals):
            if op.attrs["fn"] == "div":
                return vals[0][..., :op.out_dim]
            return real(self, op, vals)
        monkeypatch.setattr(pipeline._Executor, "_eltwise", eltwise)


def _control(fn, params):
    """The served logits replaced by the control's: every dense matmul
    as three bfloat16 products."""
    ref = run.reference(CFG)

    def call(feeds):
        data = {k: np.asarray(v) for k, v in feeds.items()}
        with jax.default_matmul_precision("highest"):
            return {"logits": ref.expected(params, data, SMALL,
                                           control=True)["heads"]["logits"]}
    return call


@pytest.mark.parametrize("fault", ["train_bn", "ungated_sum", "bf16_control"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    if fault == "bf16_control":
        out = _run(52, fault=_control)
    else:
        _plant_in_executor(monkeypatch, fault)
        out = _run(52)
    assert not out["correct"], out["compared"]
    gap = out["compared"]["head_gap"]
    assert gap["value"] > gap["limit"], gap


def test_model_flops_hand_count():
    """40 hits and 320 edges at the published widths: per layer the
    four node matrices (4·2·40·70²), C on the edges (2·320·70²) and the
    two segment sums (2·320·70); the embeddings (2·40·4·70 and
    2·320·1·70) and the readout 70→35→17→3 (2·40·(2450 + 595 + 51))."""
    ref = run.reference(CFG)
    layer = 4 * 2 * 40 * 4900 + 2 * 320 * 4900 + 2 * 320 * 70
    want = (16 * layer + 2 * 40 * 4 * 70 + 2 * 320 * 70
            + 2 * 40 * (2450 + 595 + 51))
    assert ref.model_flops(40, CFG["widths"]) == want == 76_295_680
