import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import — jax locks the device
# count at first init.  REPRO_DRYRUN_DEVICES overrides for small local tests.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=" + os.environ["REPRO_DRYRUN_DEVICES"]
    )

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

import jax  # noqa: E402

from repro.analysis import roofline  # noqa: E402
from repro.analysis.hlo_collectives import collective_sites, collective_stats  # noqa: E402
from repro.analysis.jaxpr_cost import step_cost  # noqa: E402
from repro.configs.registry import all_cells, get_arch  # noqa: E402
from repro.launch.cells import build_cell  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402


def _analytic_shard_bytes(args, shardings) -> int:
    """Per-device bytes of the (sharded) inputs, from NamedSharding math."""
    total = 0
    for sds, sh in zip(
        jax.tree_util.tree_leaves(args), jax.tree_util.tree_leaves(shardings)
    ):
        shard_shape = sh.shard_shape(sds.shape)
        total += int(np.prod(shard_shape)) * sds.dtype.itemsize
    return total


def _memory_analysis_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # CPU backend may not implement it
        return {"unavailable": str(e)[:200]}
    out = {}
    for attr in dir(ma):
        if attr.startswith("_"):
            continue
        try:
            v = getattr(ma, attr)
        except Exception:
            continue
        if isinstance(v, (int, float)):
            out[attr] = v
    return out or {"repr": repr(ma)[:500]}


def run_cell(arch_id: str, shape_id: str, multi_pod: bool, outdir: Path, *, mesh=None, sites: bool = False, strategy: str = "default") -> dict:
    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    mesh_tag = "x".join(str(s) for s in mesh.devices.shape)
    cell = build_cell(arch_id, shape_id, mesh, strategy)

    t0 = time.time()
    with mesh:
        jitted = jax.jit(
            cell.step_fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
        )
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    raw_flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    # XLA cost analysis counts while/scan bodies once (verified; see
    # analysis/jaxpr_cost.py) — use the scan-aware jaxpr walker instead.
    est = step_cost(cell.step_fn, *cell.args)
    flops = est["mxu_flops"] / n_chips  # global -> per-chip (work is sharded)
    vpu = est["vpu_flops"] / n_chips
    bytes_accessed = est["bytes"] / n_chips
    hlo = compiled.as_text()
    coll_stats = collective_stats(hlo)
    coll_bytes = roofline.collective_bytes(coll_stats)
    site_rows = collective_sites(hlo) if sites else None
    mem = _memory_analysis_dict(compiled)
    terms = roofline.roofline_terms(flops, bytes_accessed, coll_bytes, vpu)

    record = {
        "arch": arch_id,
        "shape": shape_id,
        "kind": cell.kind,
        "mesh": mesh_tag,
        "n_chips": n_chips,
        "multi_pod": multi_pod,
        "strategy": strategy,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "hlo_flops_per_chip": flops,
        "vpu_flops_per_chip": vpu,
        "hlo_bytes_per_chip": bytes_accessed,
        "raw_cost_analysis_flops": raw_flops,
        "raw_cost_analysis_bytes": raw_bytes,
        "est_flops_global": est["flops"],
        "est_bytes_global": est["bytes"],
        "collective_bytes_per_chip": coll_bytes,
        "collectives": coll_stats,
        "collective_sites": site_rows,
        "memory_analysis": mem,
        "arg_bytes_per_chip": _analytic_shard_bytes(cell.args, cell.in_shardings),
        "model_flops_global": cell.model_flops,
        "model_flops_per_chip": cell.model_flops / n_chips,
        "useful_flops_ratio": (cell.model_flops / est["mxu_flops"]) if est["mxu_flops"] else None,
        "roofline": terms,
    }
    outdir.mkdir(parents=True, exist_ok=True)
    fname = f"{arch_id.replace('/', '_')}__{shape_id}__{mesh_tag}.json"
    (outdir / fname).write_text(json.dumps(record, indent=1))
    print(roofline.summarize(record), f"(compile {t_compile:.1f}s)", flush=True)
    return record


def main():
    ap = argparse.ArgumentParser(description="Multi-pod dry-run: lower+compile every cell")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true", help="run every assigned cell")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--sites", action="store_true", help="attribute collective bytes to op_names")
    ap.add_argument("--strategy", default="default", help="sharding strategy (tp_sp|zero_dp|nodes_sharded|nodes_replicated)")
    args = ap.parse_args()

    if args.list:
        for a, s in all_cells():
            print(a, s)
        return

    outdir = Path(args.out)
    cells = all_cells() if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch_id, shape_id in cells:
        if arch_id is None or shape_id is None:
            raise SystemExit("need --arch and --shape (or --all)")
        for mp in meshes:
            tag = "2x16x16" if mp else "16x16"
            fname = outdir / f"{arch_id}__{shape_id}__{tag}.json"
            if args.skip_existing and fname.exists():
                print("skip", fname.name, flush=True)
                continue
            try:
                run_cell(arch_id, shape_id, mp, outdir, sites=args.sites, strategy=args.strategy)
            except Exception as e:
                failures.append((arch_id, shape_id, tag, repr(e)))
                print(f"FAIL {arch_id}/{shape_id}@{tag}: {e}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", *f)
        raise SystemExit(1)
    print("\nall requested cells compiled OK")


if __name__ == "__main__":
    main()
