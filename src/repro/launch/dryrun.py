import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver.

Lowers + compiles every (architecture × input-shape × mesh) cell against the
production meshes (16x16 single pod, 2x16x16 multi-pod) using ShapeDtypeStruct
inputs only (no allocation), then records memory_analysis / cost_analysis /
collective-byte accounting for EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh pod
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh pod|multipod|both]
Results accumulate in dryrun_results.json (one entry per cell; idempotent).
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_arch, list_archs
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_cell
from repro.roofline.analysis import from_compiled
from repro.roofline.hlo import parse_collectives

RESULTS = Path(__file__).resolve().parents[3] / "dryrun_results.json"


def run_cell(arch_id: str, shape_name: str, mesh_name: str,
             keep_hlo: bool = False) -> dict:
    spec = get_arch(arch_id)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    chips = int(np.prod(list(mesh.shape.values())))
    cell = build_cell(spec, shape_name, mesh, use_full=True)

    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            cell.step_fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
        )
        lowered = jitted.lower(*cell.args_spec)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    # -- memory ---------------------------------------------------------------
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)
    except Exception as e:  # CPU backend may not implement it
        mem["error"] = str(e)
    # logical per-chip bytes from shardings (backend-independent)
    mem["args_logical_bytes_per_chip"] = _logical_bytes(cell, mesh)

    # -- cost + collectives ----------------------------------------------------
    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        cost = {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and k in
                ("flops", "bytes accessed", "transcendentals",
                 "utilization operand 0 {}", "optimal_seconds")}
    except Exception as e:
        cost = {"error": str(e)}
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)

    roof = from_compiled(
        arch_id, shape_name, mesh_name, chips,
        cost if "error" not in cost else None,
        coll.link_bytes, coll.counts, cell.model_flops,
    )
    out = {
        "arch": arch_id, "shape": shape_name, "mesh": mesh_name,
        "kind": cell.kind, "chips": chips,
        "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
        "memory": mem, "cost": cost,
        "collectives": coll.to_dict(),
        "model_flops": cell.model_flops,
        "meta": cell.meta,
        "roofline": roof.to_dict(),
        "ok": True,
    }
    if keep_hlo:
        hdir = RESULTS.parent / "hlo"
        hdir.mkdir(exist_ok=True)
        (hdir / f"{arch_id}__{shape_name}__{mesh_name}.txt").write_text(hlo)
    return out


def _measure(cell, mesh) -> dict:
    """Lower+compile a (calibration) cell and return flops/bytes/collectives."""
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell.step_fn,
                         in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings)
        compiled = jitted.lower(*cell.args_spec).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    coll = parse_collectives(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "link_bytes": coll.link_bytes,
        "counts": coll.counts,
    }


def calibrate_cell(arch_id: str, shape_name: str, mesh_name: str) -> dict:
    """Exact per-step flops/bytes/collective accounting.

    ``cost_analysis`` does not multiply while-loop bodies by trip count, so the
    production lowering (scan-over-layers + chunked attention/loss) undercounts.
    We re-lower with all scans unrolled: recsys/GNN-small exactly; LM and GNN
    via depth-{1,2} unrolled lowerings and linear extrapolation in layers
    (every layer is identical, so v(L) = v1 + (L-1)(v2-v1) is exact)."""
    import dataclasses as dc

    spec = get_arch(arch_id)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    full = spec.full

    # anchors at L=2,3: the L=1 lowering triggers anomalous SPMD resharding
    # copies that break linearity (verified empirically: L in {2,3,...} is
    # linear per layer to <2%)
    if spec.family == "lm":
        def mk(L):
            return dc.replace(full, n_layers=L, scan_layers=False,
                              unroll_scans=True)
        m1 = _measure(build_cell(spec, shape_name, mesh, cfg_override=mk(2)), mesh)
        m2 = _measure(build_cell(spec, shape_name, mesh, cfg_override=mk(3)), mesh)
        return _extrapolate(m1, m2, full.n_layers, anchors=(2, 3))
    if spec.family == "gnn":
        def mk(L):
            return dc.replace(full, n_layers=L, scan_blocks=False)
        m1 = _measure(build_cell(spec, shape_name, mesh, cfg_override=mk(2)), mesh)
        m2 = _measure(build_cell(spec, shape_name, mesh, cfg_override=mk(3)), mesh)
        return _extrapolate(m1, m2, full.n_layers, anchors=(2, 3))
    # recsys: unroll everything (models are shallow) -> exact
    if arch_id in ("dien", "bert4rec", "dlrm-uih"):
        cfg = dc.replace(full, unroll_scans=True)
        return _measure(build_cell(spec, shape_name, mesh, cfg_override=cfg), mesh)
    # two-tower / dcn-v2 have no scans: production lowering is already exact
    return _measure(build_cell(spec, shape_name, mesh), mesh)


def _extrapolate(m1: dict, m2: dict, n_layers: int,
                 anchors=(1, 2)) -> dict:
    a1, a2 = anchors
    out = {}
    for k in ("flops", "bytes", "link_bytes"):
        slope = max(0.0, (m2[k] - m1[k]) / (a2 - a1))
        out[k] = m1[k] + (n_layers - a1) * slope
    counts = {}
    for op in set(m1["counts"]) | set(m2["counts"]):
        c1, c2 = m1["counts"].get(op, 0), m2["counts"].get(op, 0)
        counts[op] = c1 + (n_layers - a1) * max(0, (c2 - c1) // (a2 - a1))
    out["counts"] = counts
    out["extrapolated_from"] = list(anchors)
    return out


def run_calibration(arch_id: str, shape_name: str, mesh_name: str) -> dict:
    cal = calibrate_cell(arch_id, shape_name, mesh_name)
    spec = get_arch(arch_id)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    chips = int(np.prod(list(mesh.shape.values())))
    cell = build_cell(spec, shape_name, mesh)
    roof = from_compiled(
        arch_id, shape_name, mesh_name, chips,
        {"flops": cal["flops"], "bytes accessed": cal["bytes"]},
        cal["link_bytes"], cal["counts"], cell.model_flops,
    )
    return {"calibration": cal, "roofline_calibrated": roof.to_dict(),
            "model_flops": cell.model_flops}


def _logical_bytes(cell, mesh) -> int:
    """Per-chip bytes of all step inputs under their PartitionSpecs."""
    chips = int(np.prod(list(mesh.shape.values())))
    total = 0

    def leaf_bytes(leaf, spec):
        n = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        shard = 1
        entries = list(spec) if spec is not None else []
        for e in entries:
            for ax in (e if isinstance(e, tuple) else (e,)):
                if ax is not None:
                    shard *= mesh.shape[ax]
        return n // max(shard, 1)

    from jax.sharding import PartitionSpec as P
    for args, shs in zip(cell.args_spec, cell.in_shardings):
        leaves, _ = jax.tree_util.tree_flatten(args)
        specs, _ = jax.tree_util.tree_flatten(
            shs, is_leaf=lambda x: isinstance(x, P) or x is None)
        if len(leaves) == len(specs):
            total += sum(leaf_bytes(l, s) for l, s in zip(leaves, specs))
        else:
            total += sum(int(np.prod(l.shape)) * l.dtype.itemsize // chips
                         for l in leaves)
    return total


def load_results() -> dict:
    if RESULTS.exists():
        return json.loads(RESULTS.read_text())
    return {}


def save_result(key: str, entry: dict) -> None:
    res = load_results()
    res[key] = entry
    RESULTS.write_text(json.dumps(res, indent=1, default=str))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--calibrate", action="store_true",
                    help="add exact (unrolled/extrapolated) roofline terms")
    args = ap.parse_args()

    assert jax.device_count() == 512, (
        f"dry-run needs 512 host devices, got {jax.device_count()} — "
        "XLA_FLAGS must be set before jax import")

    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    done = load_results()
    failures = []
    for arch_id in archs:
        spec = get_arch(arch_id)
        shapes = [args.shape] if args.shape else list(spec.shapes)
        for shape_name in shapes:
            for mesh_name in meshes:
                key = f"{arch_id}|{shape_name}|{mesh_name}"
                if args.calibrate:
                    entry = done.get(key)
                    if not (entry and entry.get("ok")):
                        print(f"[skip] {key} (no baseline)")
                        continue
                    if "roofline_calibrated" in entry and not args.force:
                        print(f"[skip] {key} (calibrated)")
                        continue
                    print(f"[cal ] {key} ...", flush=True)
                    try:
                        entry.update(run_calibration(arch_id, shape_name,
                                                     mesh_name))
                        r = entry["roofline_calibrated"]
                        print(f"[ ok ] {key}: bottleneck={r['bottleneck']} "
                              f"frac={r['roofline_fraction']:.3f} "
                              f"useful={r['model_flops_ratio']:.2f}", flush=True)
                    except Exception as e:
                        failures.append(key)
                        entry["calibration_error"] = f"{type(e).__name__}: {e}"
                        print(f"[FAIL] {key}: {type(e).__name__}: {e}",
                              flush=True)
                    save_result(key, entry)
                    continue
                if key in done and done[key].get("ok") and not args.force:
                    print(f"[skip] {key}")
                    continue
                print(f"[run ] {key} ...", flush=True)
                try:
                    entry = run_cell(arch_id, shape_name, mesh_name,
                                     keep_hlo=args.keep_hlo)
                    r = entry["roofline"]
                    print(f"[ ok ] {key}: compile={entry['t_compile_s']}s "
                          f"bottleneck={r['bottleneck']} "
                          f"frac={r['roofline_fraction']:.3f}", flush=True)
                except Exception as e:
                    entry = {"arch": arch_id, "shape": shape_name,
                             "mesh": mesh_name, "ok": False,
                             "error": f"{type(e).__name__}: {e}",
                             "traceback": traceback.format_exc()[-3000:]}
                    failures.append(key)
                    print(f"[FAIL] {key}: {type(e).__name__}: {e}", flush=True)
                save_result(key, entry)
    if failures:
        print(f"\n{len(failures)} failures: {failures}")
        raise SystemExit(1)
    print("\nall cells compiled")


if __name__ == "__main__":
    main()
