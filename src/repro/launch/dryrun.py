"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape) cell, against both production meshes
(single-pod 16x16 and multi-pod 2x16x16):

    with mesh:
        lowered = jax.jit(step, in_shardings=..., out_shardings=...,
                          donate_argnums=...).lower(*input_specs(cell))
        compiled = lowered.compile()
        compiled.memory_analysis()   # proves it fits
        compiled.cost_analysis()     # FLOPs/bytes for the roofline

Results accumulate into a JSON file consumed by EXPERIMENTS.md's §Dry-run /
§Roofline tables and by benchmarks/roofline_summary.

``main`` asks for 512 host devices through XLA_FLAGS before anything
touches a jax backend (jax fixes the device count when the backend starts);
importing this module changes nothing.
"""

import argparse
import json
import os
import time
import traceback
from pathlib import Path

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import list_archs
from repro.configs.shapes import SHAPES, LONG_CTX_ARCHS, cells_for
from repro.launch import roofline as rf
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs, microbatches_for, step_fn_for
from repro.launch.steps import TrainState
from repro.models import sharding as shd
from repro.models.pspec import activation_mesh, unrolled_scans


def shardings_for(kind, cfg, args, mesh):
    """in/out shardings + donation matching the step signature."""
    if kind == "train":
        state, batch = args
        pspec = shd.param_specs(state.params, cfg, mesh)
        opt_spec = shd.opt_state_specs(state.opt, pspec, mesh)
        state_spec = TrainState(params=pspec, opt=opt_spec, step=P())
        in_specs = (state_spec, shd.batch_specs(batch, mesh))
        out_specs = (state_spec, P())  # metrics replicated
        donate = (0,)
    elif kind == "prefill":
        params, batch = args
        pspec = shd.param_specs(params, cfg, mesh)
        in_specs = (pspec, shd.batch_specs(batch, mesh))
        out_specs = None  # logits: let GSPMD place (batch, None, vocab/model)
        donate = ()
    else:  # decode
        params, cache, tok = args
        pspec = shd.param_specs(params, cfg, mesh)
        cspec = shd.cache_specs(cache, cfg, mesh)
        in_specs = (pspec, cspec, shd.batch_specs({"t": tok}, mesh)["t"])
        out_specs = (None, cspec)
        donate = (1,)
    return in_specs, out_specs, donate


#: full-depth unrolled lowering is used up to this many layers; deeper
#: stacks use the two-point extrapolation (per-layer cost is uniform inside
#: each scanned stack, so cost(L) is exactly linear in L for congruent L).
UNROLL_MAX_LAYERS = 14


def _depth_points(cfg) -> tuple[int, int]:
    """Two depths L1 < L2, congruent to num_layers modulo the arch's layer
    period and preserving the dense prefix, so cost(L) is linear on
    {L1, L2, L}."""
    period = cfg.hybrid_attn_period or cfg.local_global_period or 1
    base = cfg.first_dense_layers
    residue = (cfg.num_layers - base) % period
    k1, k2 = (4, 8) if period == 1 else (1, 2)
    l1 = base + k1 * period + residue
    l2 = base + k2 * period + residue
    if l2 >= cfg.num_layers:
        return cfg.num_layers, cfg.num_layers  # too shallow: no extrapolation
    return l1, l2


def _scaled_cfg(cfg, n_layers: int):
    import dataclasses

    reps = {"num_layers": n_layers}
    if cfg.encoder_decoder and cfg.encoder_layers:
        reps["encoder_layers"] = max(
            1, round(cfg.encoder_layers * n_layers / cfg.num_layers)
        )
    return dataclasses.replace(cfg, **reps)


def _lower_cost(arch, shape, kind, cfg, mesh, *, reduced):
    """Unrolled µ=1 compile for one (possibly depth-scaled) config; returns
    (flops, bytes, coll_by_kind) per device."""
    spec = input_specs(arch, shape, reduced=reduced, cfg_override=cfg)
    args = spec["args"]
    step = step_fn_for(kind, cfg, num_microbatches=1)
    in_specs, out_specs, donate = shardings_for(kind, cfg, args, mesh)
    to_shd = lambda t: jax.tree.map(
        lambda s: NamedSharding(mesh, s), t, is_leaf=lambda x: isinstance(x, P)
    )
    jit_kwargs = dict(in_shardings=to_shd(in_specs), donate_argnums=donate)
    if out_specs is not None:
        jit_kwargs["out_shardings"] = to_shd(out_specs)
    with mesh, activation_mesh(mesh), unrolled_scans():
        compiled = jax.jit(step, **jit_kwargs).lower(*args).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    txt = compiled.as_text()
    colls = rf.collective_bytes(txt)
    byts = max(
        0.0, float(ca.get("bytes accessed", 0.0)) - rf.dus_overcount(txt)
    )
    return float(ca.get("flops", 0.0)), byts, colls


def _cost_terms(arch, shape, kind, cfg, mesh, *, reduced):
    """(flops, bytes, coll_by_kind, method) per device — direct unrolled
    compile for shallow stacks, two-point depth extrapolation for deep ones."""
    l1, l2 = _depth_points(cfg)
    if cfg.num_layers <= UNROLL_MAX_LAYERS or l1 == l2:
        f, b, c = _lower_cost(arch, shape, kind, cfg, mesh, reduced=reduced)
        return f, b, c, "unrolled-full"
    f1, b1, c1 = _lower_cost(
        arch, shape, kind, _scaled_cfg(cfg, l1), mesh, reduced=reduced
    )
    f2, b2, c2 = _lower_cost(
        arch, shape, kind, _scaled_cfg(cfg, l2), mesh, reduced=reduced
    )
    t = (cfg.num_layers - l1) / (l2 - l1)
    lerp = lambda a, b: a + t * (b - a)
    kinds = set(c1) | set(c2)
    colls = {k: max(0.0, lerp(c1.get(k, 0), c2.get(k, 0))) for k in kinds}
    return lerp(f1, f2), lerp(b1, b2), colls, f"extrapolated:{l1},{l2}"


def run_cell(arch: str, shape: str, mesh_kind: str, *, reduced: bool = False) -> dict:
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    spec = input_specs(arch, shape, reduced=reduced)
    kind, cfg, args = spec["kind"], spec["cfg"], spec["args"]
    sh = SHAPES[shape]
    mu = microbatches_for(kind, cfg, sh.global_batch, sh.seq_len, mesh)
    step_mem = step_fn_for(kind, cfg, num_microbatches=mu)

    in_specs, out_specs, donate = shardings_for(kind, cfg, args, mesh)
    to_shd = lambda t: jax.tree.map(
        lambda s: NamedSharding(mesh, s), t, is_leaf=lambda x: isinstance(x, P)
    )
    jit_kwargs = dict(in_shardings=to_shd(in_specs), donate_argnums=donate)
    if out_specs is not None:
        jit_kwargs["out_shardings"] = to_shd(out_specs)

    t0 = time.time()
    # TWO passes per cell:
    #  * rolled scans, µ-batched, FULL depth -> memory_analysis (buffer reuse
    #    across layers/microbatches = the realistic steady-state footprint);
    #  * unrolled µ=1 cost pass -> cost_analysis + collective parse (XLA
    #    counts a while-loop body ONCE regardless of trip count — see
    #    models/pspec.py — so true per-step FLOPs/bytes/collective traffic
    #    need unrolled modules; deep stacks extrapolate from two depths).
    with mesh, activation_mesh(mesh):
        jitted = jax.jit(step_mem, **jit_kwargs)
        compiled_rolled = jitted.lower(*args).compile()
    t_rolled = time.time() - t0
    flops, byts, colls, method = _cost_terms(
        arch, shape, kind, cfg, mesh, reduced=reduced
    )
    t_compile = time.time() - t0 - t_rolled

    ma = compiled_rolled.memory_analysis()
    counts = cfg.param_counts()
    tokens = sh.global_batch * (sh.seq_len if kind != "decode" else 1)
    mult = 3.0 if kind == "train" else 1.0  # fwd+bwd
    model_flops_global = 2.0 * counts["active"] * tokens * mult
    n_dev = mesh.size
    report = rf.roofline_from_terms(
        flops, byts, colls,
        model_flops_global=model_flops_global, num_devices=n_dev,
    )

    out = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_kind,
        "kind": kind,
        "devices": n_dev,
        "microbatches": mu,
        "cost_method": method,
        "compile_s": round(t_compile, 1),
        "compile_rolled_s": round(t_rolled, 1),
        "memory": {
            "argument_bytes_per_dev": int(ma.argument_size_in_bytes),
            "output_bytes_per_dev": int(ma.output_size_in_bytes),
            "temp_bytes_per_dev": int(ma.temp_size_in_bytes),
            "alias_bytes_per_dev": int(ma.alias_size_in_bytes),
            "peak_bytes_per_dev": int(
                ma.argument_size_in_bytes
                + ma.output_size_in_bytes
                + ma.temp_size_in_bytes
                - ma.alias_size_in_bytes
            ),
        },
        "roofline": report.to_json(),
    }
    return out


def main() -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list_archs() + ["all"], default="all")
    ap.add_argument("--shape", choices=list(SHAPES) + ["all"], default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke variant (small dims) — for CI only")
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else [args.arch]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results = {}
    if out_path.exists():
        results = json.loads(out_path.read_text())

    for arch in archs:
        shapes = (
            [s for _, s in cells_for(arch)] if args.shape == "all" else [args.shape]
        )
        for shape in shapes:
            if shape == "long_500k" and arch not in LONG_CTX_ARCHS:
                print(f"SKIP {arch} x {shape} (full attention; DESIGN.md §5)")
                results[f"{arch}|{shape}|-"] = {"skip": True}
                continue
            for mesh_kind in meshes:
                key = f"{arch}|{shape}|{mesh_kind}"
                if results.get(key) and not results[key].get("error"):
                    print(f"CACHED {key}")
                    continue
                print(f"RUN {key} ...", flush=True)
                try:
                    cell = run_cell(arch, shape, mesh_kind, reduced=args.reduced)
                    results[key] = cell
                    r = cell["roofline"]
                    print(
                        f"  ok: compile={cell['compile_s']}s "
                        f"peak={cell['memory']['peak_bytes_per_dev']/2**30:.2f}GiB/dev "
                        f"compute={r['compute_s']*1e3:.2f}ms "
                        f"memory={r['memory_s']*1e3:.2f}ms "
                        f"coll={r['collective_s']*1e3:.2f}ms "
                        f"dom={r['dominant']}",
                        flush=True,
                    )
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    results[key] = {"error": f"{type(e).__name__}: {e}"}
                out_path.write_text(json.dumps(results, indent=1))
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
