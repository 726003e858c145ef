"""End-to-end training driver: feature store -> PIT batches -> train loop,
with checkpoint/restart fault tolerance.

    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --reduced \
        --steps 200 --batch 8 --seq 256 --ckpt-dir /tmp/run1

Fault-tolerance demo: add ``--kill-at 120`` to simulate a node failure at
step 120, then re-run the same command — the driver restores the latest
checkpoint (train state + scheduler state + loader clock) and continues to
--steps, bit-identically to an uninterrupted run (tested in
tests/integration/test_train_driver.py).

On a real cluster the same driver runs under the production mesh: pass
--mesh dxm (e.g. --mesh 4x2) to shard over hosts' devices.
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config, list_archs
from repro.data.loader import HOUR, FeatureStoreLoader, TokenFeatureSet
from repro.data.sources import TokenEventSource
from repro.core.featurestore import FeatureStore
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.steps import TrainState, make_train_step
from repro.models import api
from repro.models.pspec import activation_mesh
from repro.models import sharding as shd
from repro.optim.adamw import adamw
from repro.optim.schedules import warmup_cosine


def build_data_plane(cfg, *, seq_len: int, batch: int, seed: int = 0):
    src = TokenEventSource(
        "token_stream", seed=seed, vocab_size=cfg.vocab_size,
        num_docs=256, chunk_len=64, chunks_per_bucket=512,
    )
    fs = FeatureStore("lm-data-plane")
    fs.register_source(src)
    spec = fs.create_feature_set(TokenFeatureSet(src))
    loader = FeatureStoreLoader(
        store=fs, spec=spec, seq_len=seq_len, batch_size=batch,
        chunk_len=src.chunk_len, seed=seed,
    )
    return fs, loader


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="gemma-2b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--kill-at", type=int, default=0,
                    help="simulate node failure at this step")
    ap.add_argument("--mesh", default="", help="dxm, e.g. 4x2 (default: none)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=args.reduced)
    fs, loader = build_data_plane(cfg, seq_len=args.seq, batch=args.batch,
                                  seed=args.seed)
    loader.advance(6 * HOUR)

    optimizer = adamw(
        lr=warmup_cosine(args.lr, 20, args.steps), weight_decay=0.01,
        quantize_moments=False,
    )
    train_step = make_train_step(cfg, optimizer)

    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))

    params = api.init_params(jax.random.PRNGKey(args.seed), cfg)
    state = TrainState.create(params, optimizer)

    ckpt = CheckpointManager(args.ckpt_dir, every=args.ckpt_every) if args.ckpt_dir else None
    start_step = 0
    if ckpt:
        restored = ckpt.restore_latest(state)
        if restored[0] is not None:
            saved_step, state, extra = restored
            start_step = saved_step + 1  # state is AFTER executing saved_step
            loader.load_state_dict(extra["loader"])
            fs.restore_scheduler(extra["scheduler"])
            print(f"[train] restored checkpoint at step {saved_step}")

    if mesh is not None:
        pspec = shd.param_specs(state.params, cfg, mesh)
        from jax.sharding import NamedSharding, PartitionSpec as P

        sspec = TrainState(
            params=pspec, opt=shd.opt_state_specs(state.opt, pspec), step=P()
        )
        to_shd = lambda t: jax.tree.map(
            lambda s: NamedSharding(mesh, s), t, is_leaf=lambda x: isinstance(x, P)
        )
        state = jax.device_put(state, to_shd(sspec))
        jitted = jax.jit(train_step, in_shardings=(to_shd(sspec), None),
                         out_shardings=(to_shd(sspec), None),
                         donate_argnums=(0,))
    else:
        jitted = jax.jit(train_step, donate_argnums=(0,))

    losses = []
    t0 = time.time()
    ctx = activation_mesh(mesh) if mesh is not None else _null_ctx()
    with ctx:
        for step in range(start_step, args.steps):
            if args.kill_at and step == args.kill_at:
                print(f"[train] simulated node failure at step {step}")
                raise SystemExit(17)
            batch = loader.sample_batch(step)
            model_batch = {"tokens": jax.numpy.asarray(batch["tokens"])}
            if cfg.encoder_decoder or cfg.vision_prefix:
                dummy = api.make_dummy_batch(cfg, args.batch, args.seq, seed=step)
                for k in ("frames", "patch_embeds"):
                    if k in dummy:
                        model_batch[k] = dummy[k]
            state, metrics = jitted(state, model_batch)
            losses.append(float(metrics["lm_loss"]))
            if step % args.log_every == 0:
                print(
                    f"[train] step {step:5d} loss {losses[-1]:.4f} "
                    f"({(time.time()-t0):.1f}s)", flush=True,
                )
            if ckpt:
                ckpt.maybe_save(
                    step, state,
                    extra={"loader": loader.state_dict(),
                           "scheduler": fs.scheduler_state()},
                )
    result = {
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
        "steps_run": len(losses),
        "start_step": start_step,
        "losses": losses,
    }
    if losses:
        print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return result


class _null_ctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    enable_compile_cache()
    main()
