"""§Perf iteration probe: compile ONE depth-scaled cell, print roofline terms
+ collective sites + top tensors, and append to results/perf_iters/<tag>.json.

    PYTHONPATH=src python scripts/perf_probe.py --arch deepseek-v3-671b \
        --shape train_4k --layers 5 --tag ds3_iter3_ep_boundary
"""

import argparse
import json
import os
import time
from pathlib import Path

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--layers", type=int, default=0, help="depth override (0=full)")
    ap.add_argument("--tag", required=True)
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--memory-pass", action="store_true",
                    help="also run the rolled µ-batched memory pass")
    args = ap.parse_args()
    # 512 host devices for the production mesh; set before anything starts a
    # jax backend, which fixes the device count
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

    from repro.configs import get_config
    from repro.configs.shapes import SHAPES
    from repro.launch import dryrun, hlo_tools
    from repro.launch import roofline as rf
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import input_specs, microbatches_for, step_fn_for
    from repro.models.pspec import activation_mesh, unrolled_scans

    mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    cfg = get_config(args.arch)
    if args.layers:
        cfg = dryrun._scaled_cfg(cfg, args.layers)
    spec = input_specs(args.arch, args.shape, cfg_override=cfg)
    kind, cargs = spec["kind"], spec["args"]
    step = step_fn_for(kind, cfg, num_microbatches=1)
    in_specs, out_specs, donate = dryrun.shardings_for(kind, cfg, cargs, mesh)
    to_shd = lambda t: jax.tree.map(
        lambda s: NamedSharding(mesh, s), t, is_leaf=lambda x: isinstance(x, P)
    )
    kw = dict(in_shardings=to_shd(in_specs), donate_argnums=donate)
    if out_specs is not None:
        kw["out_shardings"] = to_shd(out_specs)

    t0 = time.time()
    with mesh, activation_mesh(mesh), unrolled_scans():
        compiled = jax.jit(step, **kw).lower(*cargs).compile()
    compile_s = time.time() - t0

    report = rf.roofline_from_compiled(compiled, num_devices=mesh.size)
    txt = compiled.as_text()
    colls = hlo_tools.collective_sites(txt, k=10)
    tops = hlo_tools.top_tensors(txt, k=10)

    out = {
        "tag": args.tag,
        "arch": args.arch,
        "shape": args.shape,
        "layers": args.layers or cfg.num_layers,
        "mesh": args.mesh,
        "compile_s": round(compile_s, 1),
        "roofline": report.to_json(),
        "collective_sites": colls,
        "top_tensors": [
            {"shape": s, "GiB": round(b / 2**30, 3), "count": c}
            for s, b, c in tops
        ],
    }

    if args.memory_pass:
        sh = SHAPES[args.shape]
        mu = microbatches_for(kind, cfg, sh.global_batch, sh.seq_len, mesh)
        step_m = step_fn_for(kind, cfg, num_microbatches=mu)
        with mesh, activation_mesh(mesh):
            cm = jax.jit(step_m, **kw).lower(*cargs).compile()
        ma = cm.memory_analysis()
        out["memory_pass"] = {
            "microbatches": mu,
            "peak_GiB_per_dev": round(
                (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30, 2
            ),
            "temp_GiB": round(ma.temp_size_in_bytes / 2**30, 2),
        }

    r = out["roofline"]
    print(f"[{args.tag}] compile={compile_s:.0f}s "
          f"compute={r['compute_s']*1e3:.1f}ms memory={r['memory_s']*1e3:.1f}ms "
          f"collective={r['collective_s']*1e3:.1f}ms dom={r['dominant']}")
    for s in colls[:6]:
        print(f"  coll {s['kind']:18s} {s['shape']:50s} n={s['count']:4d} "
              f"{s['bytes']/2**30:7.2f} GiB")
    for t in out["top_tensors"][:6]:
        print(f"  top  {t['shape']:50s} {t['GiB']:8.3f} GiB x{t['count']}")
    if "memory_pass" in out:
        print(f"  mem-pass µ={out['memory_pass']['microbatches']} "
              f"peak={out['memory_pass']['peak_GiB_per_dev']} GiB/dev")

    p = Path(f"results/perf_iters/{args.tag}.json")
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(out, indent=1))
    print("wrote", p)


if __name__ == "__main__":
    main()
