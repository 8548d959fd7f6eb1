"""Shared differential-numerics assertions for the kernel test suite.

Every fused-vs-unfused and kernel-vs-oracle comparison in the tests
used to carry its own copy of the tolerance logic (bitwise for f32
column splits, rtol/atol pairs per dtype, looser bounds for bf16).
With the quantized megakernel adding a third comparison regime —
*calibration tolerance*, where independently derived requantization
grids may legitimately disagree by whole quantization steps on
boundary values — the logic lives here once, so every test states
**which** equivalence it claims instead of re-inventing bounds:

- ``assert_bitwise``       : exact equality — fused rewrites that
                             reassociate nothing (column splits, the
                             f32 megakernel vs its unfused chain).
- ``assert_close``         : dtype-derived rtol/atol — kernel vs eager
                             oracle where jit fusion may move last
                             ulps (pass ``dtype=`` or explicit tols).
- ``assert_ulp_close``     : bounded ulp distance for f32 — tighter
                             than rtol/atol near zero, used for
                             K-reduction splits.
- ``assert_calibration_close``: the int8 regime — requires agreement
                             up to a caller-computed requantization
                             quantum and a small fraction of affected
                             elements (``int8_flip_tolerance`` derives
                             the quantum from the baked scales).
- ``assert_selection_close``: two deployed programs that select
                             k-NN neighbors from coordinates computed
                             in a different order — every hit within
                             the dtype's tolerance except a measured
                             share whose selection flipped at a
                             near-tie, and those within a bound.
- ``assert_fixture_match``: a golden fixture — integer, bool and
                             index arrays exactly, float arrays by
                             their dtype's rtol/atol.
- ``backend_sweep``        : the backends a differential test should
                             run — ``xla`` (jnp reference), the
                             interpret-mode Pallas body, and the real
                             ``pallas`` path when a TPU is attached.
"""
from __future__ import annotations

import jax
import numpy as np

#: dtype name -> (rtol, atol) for kernel-vs-oracle comparisons. int8
#: accumulates exactly in int32; only the elementwise dequant epilogue
#: can differ, hence the near-exact bound.
DTYPE_TOLERANCES = {
    "float32": (1e-5, 1e-5),
    "bfloat16": (3e-2, 3e-2),
    "float64": (1e-12, 1e-12),
    "int8": (1e-6, 1e-6),
}


def backend_sweep() -> tuple[str, ...]:
    """Backends a differential test should sweep: the jnp reference
    composition, the Pallas kernel body under the CPU interpreter, and
    the compiled Mosaic path when an accelerator is actually present
    (it cannot execute on CPU CI hosts)."""
    backends = ["xla", "pallas_interpret"]
    if any(d.platform == "tpu" for d in jax.devices()):
        backends.append("pallas")
    return tuple(backends)


def tolerance(dtype) -> tuple[float, float]:
    """(rtol, atol) for a dtype given as a name or a jnp/np dtype."""
    name = getattr(dtype, "__name__", None) or np.dtype(dtype).name
    return DTYPE_TOLERANCES[name]


def _as64(x):
    return np.asarray(x, np.float64)


def assert_bitwise(got, want, *, context: str = "") -> None:
    """Exact equality — the claim fused rewrites make when they
    reassociate nothing."""
    g, w = np.asarray(got), np.asarray(want)
    if np.array_equal(g, w):
        return
    d = np.abs(_as64(g) - _as64(w))
    raise AssertionError(
        f"bitwise mismatch{' (' + context + ')' if context else ''}: "
        f"{int((d > 0).sum())}/{d.size} elements differ, "
        f"max|diff|={d.max():.3e}")


def assert_close(got, want, *, dtype=None, rtol: float | None = None,
                 atol: float | None = None, context: str = "") -> None:
    """rtol/atol comparison with dtype-derived defaults. Explicit
    ``rtol``/``atol`` override the table; with neither given the
    ``got`` array's own dtype picks the row."""
    g, w = np.asarray(got), np.asarray(want)
    if rtol is None or atol is None:
        trt, tat = tolerance(dtype if dtype is not None else g.dtype)
        rtol = trt if rtol is None else rtol
        atol = tat if atol is None else atol
    np.testing.assert_allclose(_as64(g), _as64(w), rtol=rtol, atol=atol,
                               err_msg=context)


#: Selection flips between the fused and unfused f32 deployments of
#: tests/test_fusion_block.py, measured on XLA CPU at four target ISA
#: levels (``--xla_cpu_max_isa`` SSE4_2, AVX, AVX2 and AVX-512): at most
#: 3 of 128 hit rows (2.3%) in one launch, each within 1.2% of its
#: head's largest magnitude. The limits allow about twice that.
SELECTION_FLIP_FRAC = 0.05
SELECTION_FLIP_SCALE = 0.05


def assert_selection_close(got, want, *, dtype="float32",
                           context: str = "") -> None:
    """Per-hit comparison for outputs downstream of k-NN selection.
    Coordinates whose last bits differ can reorder two candidates at a
    near-tie, which changes that hit's neighbor set (and its
    neighbors' aggregates). Rows (the last axis is features) must agree
    within ``dtype``'s tolerance except at most ``SELECTION_FLIP_FRAC``
    of them, and every row must stay within ``SELECTION_FLIP_SCALE``
    times the largest ``|want|``."""
    g, w = _as64(got), _as64(want)
    assert g.shape == w.shape, (context, g.shape, w.shape)
    rtol, atol = tolerance(dtype)
    d = np.abs(g - w)
    rows = (d > atol + rtol * np.abs(w)).reshape(-1, w.shape[-1]).any(1)
    frac = float(rows.mean()) if rows.size else 0.0
    tag = f" ({context})" if context else ""
    if frac > SELECTION_FLIP_FRAC:
        raise AssertionError(
            f"selection mismatch{tag}: {frac:.1%} of hits differ beyond "
            f"{dtype} tolerance (> {SELECTION_FLIP_FRAC:.1%} allowed)")
    bound = SELECTION_FLIP_SCALE * float(np.abs(w).max(initial=0.0))
    if not d.max(initial=0.0) <= bound + atol:     # NaN fails too
        raise AssertionError(
            f"selection mismatch{tag}: max|diff|={d.max():.3e} exceeds "
            f"the flipped-hit bound {bound:.3e}")


def assert_fixture_match(got: dict, want: dict, *, dtype=None,
                         context: str = "") -> None:
    """Compare a freshly generated fixture with the committed one:
    same array names; integer/bool arrays (indices, counts, quantized
    weights, decisions) exactly; float arrays within ``dtype``'s (or
    their own dtype's) tolerance."""
    assert set(got) == set(want), (sorted(got), sorted(want))
    for name in sorted(want):
        g, w = np.asarray(got[name]), np.asarray(want[name])
        tag = f"{context}/{name}" if context else name
        if np.issubdtype(w.dtype, np.floating):
            assert g.shape == w.shape, (tag, g.shape, w.shape)
            assert_close(g, w, dtype=dtype or w.dtype, context=tag)
        else:
            assert_bitwise(g, w, context=tag)


def ulp_distance(got, want) -> np.ndarray:
    """Elementwise ulp distance between two f32 arrays, via the
    monotone int32 reinterpretation of IEEE floats (negative floats
    map below positives, so the distance is well-defined across
    zero)."""
    g = np.asarray(got, np.float32).view(np.int32).astype(np.int64)
    w = np.asarray(want, np.float32).view(np.int32).astype(np.int64)
    g = np.where(g < 0, np.int64(-(2 ** 31)) - g, g)
    w = np.where(w < 0, np.int64(-(2 ** 31)) - w, w)
    return np.abs(g - w)


def assert_ulp_close(got, want, *, max_ulp: int = 4, atol: float = 1e-6,
                     context: str = "") -> None:
    """f32 comparison in ulps — the right bound for K-reduction splits
    whose only freedom is summation order. Ulp distance diverges for
    values straddling zero (e.g. post-relu outputs a reassociated sum
    leaves at ±ε), so elements within ``atol`` absolutely pass
    regardless of their ulp distance."""
    d = ulp_distance(got, want)
    d = np.where(np.abs(_as64(got) - _as64(want)) <= atol, 0, d)
    if d.max() <= max_ulp:
        return
    raise AssertionError(
        f"ulp mismatch{' (' + context + ')' if context else ''}: "
        f"max ulp distance {int(d.max())} > {max_ulp} "
        f"({int((d > max_ulp).sum())}/{d.size} elements over)")


def int8_flip_tolerance(h_scale, wo_scale, *, flips: int = 2) -> float:
    """Worst-case output movement when requantization boundary values
    land on different sides of the grid in two implementations: each
    single-step flip of one quantized epilogue input moves an output
    element by at most ``h_scale * 127 * max(wo_scale)`` (the largest
    |int8 weight| times its channel scale). ``flips`` bounds how many
    independent flips may stack on one element."""
    return float(flips) * float(h_scale) * 127.0 * float(
        np.max(np.asarray(wo_scale, np.float64)))


def assert_calibration_close(got, want, *, quantum: float,
                             max_flip_frac: float = 0.05,
                             tight: float = 1e-5,
                             context: str = "") -> None:
    """The int8 fused-vs-unfused regime: independently derived
    requantization grids agree exactly almost everywhere, but values
    within an ulp of a grid boundary may snap to adjacent steps.
    Asserts every element is within ``quantum`` (the caller-computed
    flip bound, see ``int8_flip_tolerance``) and that at most
    ``max_flip_frac`` of elements differ by more than ``tight``."""
    d = np.abs(_as64(got) - _as64(want))
    tag = f" ({context})" if context else ""
    if d.max() > quantum + tight:
        raise AssertionError(
            f"calibration mismatch{tag}: max|diff|={d.max():.3e} exceeds "
            f"quantum bound {quantum:.3e}")
    frac = float(np.mean(d > tight))
    if frac > max_flip_frac:
        raise AssertionError(
            f"calibration mismatch{tag}: {frac:.1%} of elements flipped "
            f"(> {max_flip_frac:.1%} allowed)")
