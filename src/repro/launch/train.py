"""Production train launcher: any assigned arch, any mesh, full C/R.

On the CPU container this runs reduced configs end-to-end; on a TPU fleet
the same script runs the full configs (the mesh/sharding/dry-run machinery
is identical — that is the point of the dry-run deliverable).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b --smoke \
      --steps 50 --ckpt-dir /tmp/ck
  PYTHONPATH=src python -m repro.launch.train --arch glm4-9b --smoke \
      --steps 20 --resume --ckpt-dir /tmp/ck     # transparent restart
"""
import argparse
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager, ManagerConfig
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.data.pipeline import DataConfig, SyntheticLM, shard_batch
from repro.models.model import build_model
from repro.optim.compression import compress_tree, init_ef
from repro.train.state import init_train_state, train_state_shapes
from repro.train.steps import TrainConfig, make_train_step
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", type=Path, default=Path("/tmp/repro_ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg, q_chunk=min(64, args.seq), kv_chunk=min(64, args.seq))
    tcfg = TrainConfig(lr=args.lr, warmup_steps=10, total_steps=10_000,
                       grad_accum=args.grad_accum)
    step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0,))
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch, seed=args.seed))
    mgr = CheckpointManager(ManagerConfig(root=args.ckpt_dir / args.arch,
                                          durable_every=2))

    if args.resume and mgr.latest_step() is not None:
        state, name = mgr.restore(train_state_shapes(model, args.seed))
        print(f"resumed from {name} (step {int(state.step)})")
    else:
        state = init_train_state(model.init(jax.random.PRNGKey(args.seed)),
                                 args.seed)
        print("cold start")

    t0 = time.time()
    start_step = int(state.step)
    for i in range(args.steps):
        batch = shard_batch(data.batch_at(int(state.data_cursor)))
        # vlm/audio frontends are stubs: supply zero embeddings
        if cfg.family == "vlm":
            batch["frontend"] = jnp.zeros(
                (args.batch, cfg.vision.n_patches, cfg.vision.vision_dim),
                jnp.bfloat16)
        if cfg.family == "audio":
            batch["frontend"] = jnp.zeros(
                (args.batch, cfg.audio.n_audio_ctx, cfg.d_model), jnp.bfloat16)
        state, metrics = step_fn(state, batch)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {int(metrics['step']):5d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e}")
        if (i + 1) % args.ckpt_every == 0:
            name = mgr.save(int(state.step), state)
            print(f"checkpointed {name}")
    mgr.save(int(state.step), state, durable=True)
    dt = time.time() - t0
    tokens = (int(state.step) - start_step) * args.seq * args.batch
    print(f"done: {tokens} tokens in {dt:.1f}s ({tokens/dt:.0f} tok/s)")
    mgr.close()


if __name__ == "__main__":
    main()
