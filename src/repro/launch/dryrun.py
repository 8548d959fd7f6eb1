import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST run before any other import (jax locks
# the device count at first init). Only the dry-run sees 512 placeholder
# host devices; tests/benchmarks keep the single real CPU device.

import argparse        # noqa: E402
import json            # noqa: E402
import time            # noqa: E402
import traceback       # noqa: E402

import jax             # noqa: E402

from repro import configs                                   # noqa: E402
from repro.launch import analysis                           # noqa: E402
from repro.launch.mesh import V5E, make_production_mesh     # noqa: E402

# the production pod's chip, whose published peaks the roofline uses
TARGET_KIND = V5E
REPORT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "reports", "dryrun")


def _mem_dict(ma):
    keys = ("generated_code_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "temp_size_in_bytes",
            "alias_size_in_bytes")
    return {k: int(getattr(ma, k, 0)) for k in keys}


def run_cell(arch: str, shape: str, *, multi_pod: bool, cost_pass: bool,
             report_dir: str, force: bool = False) -> dict:
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    out_path = os.path.join(report_dir, f"{arch}__{shape}__{mesh_tag}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    mod = configs.get_arch(arch)
    cell = mod.cell(shape)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    lowered = cell.lower(mesh)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    terms = analysis.cost_terms(compiled)
    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_tag,
        "kind": cell.kind, "n_chips": n_chips,
        "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
        "memory": _mem_dict(ma),
        "per_device": {k: terms[k] for k in
                       ("flops", "bytes", "collective_bytes")},
        "collectives": terms["collectives"],
        "model_flops": cell.model_flops,
    }
    print(f"[dryrun] {arch}:{shape} @{mesh_tag}  "
          f"lower {t_lower:.1f}s compile {t_compile:.1f}s")
    print(f"  memory_analysis: {ma}")
    print(f"  cost_analysis: flops={terms['flops']:.3e} "
          f"bytes={terms['bytes']:.3e} "
          f"coll={terms['collective_bytes']:.3e}")

    # LM archs: scan-corrected cost composition (single-pod only)
    if cost_pass and mod.FAMILY == "lm" and not multi_pod:
        from repro.configs import lm_common
        quant = arch.startswith("llama4")
        ccells, l_full = lm_common.cost_cells(
            arch, mod.full_config(), shape, quantize_opt=quant)
        sub = {}
        for lred, c2 in ccells.items():
            t0 = time.time()
            comp2 = c2.lower(mesh).compile()
            sub[lred] = analysis.cost_terms(comp2)
            print(f"  cost-variant L={lred}: flops="
                  f"{sub[lred]['flops']:.3e} ({time.time()-t0:.1f}s)")
        corrected = analysis.affine_extrapolate(sub[2], sub[4], l_full)
        rec["per_device_corrected"] = corrected
        rec["cost_variants"] = {str(k): {kk: v[kk] for kk in
                                         ("flops", "bytes",
                                          "collective_bytes")}
                                for k, v in sub.items()}

    effective = rec.get("per_device_corrected", rec["per_device"])
    rec["roofline"] = analysis.roofline(effective, n_chips=n_chips,
                                        model_flops=cell.model_flops,
                                        device_kind=TARGET_KIND)
    os.makedirs(report_dir, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--no-cost-pass", action="store_true")
    ap.add_argument("--include-paper", action="store_true",
                    help="also dry-run caloclusternet cells")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--report-dir", default=os.path.normpath(REPORT_DIR))
    args = ap.parse_args()

    assert jax.device_count() == 512, \
        f"expected 512 host devices, got {jax.device_count()}"

    cells = []
    for arch, shape, mod in configs.all_cells(
            include_paper=args.include_paper):
        if args.arch and arch != args.arch:
            continue
        if args.shape and shape != args.shape:
            continue
        cells.append((arch, shape))

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = []
    for arch, shape in cells:
        for multi in meshes:
            try:
                run_cell(arch, shape, multi_pod=multi,
                         cost_pass=not args.no_cost_pass,
                         report_dir=args.report_dir, force=args.force)
            except Exception as e:  # keep going, report at end
                failures.append((arch, shape, multi, repr(e)))
                traceback.print_exc()
    print(f"\n[dryrun] {len(cells) * len(meshes) - len(failures)} ok, "
          f"{len(failures)} failed")
    for f_ in failures:
        print("  FAILED:", f_)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
