"""Training CLI driver.

Runs real steps on whatever devices exist.  On the production cluster the
same entry point runs under the (16,16) / (2,16,16) mesh (mesh.py); on this
host it runs reduced configs for end-to-end validation.

  PYTHONPATH=src python -m repro.launch.train --arch chatglm3_6b --smoke \\
      --steps 100 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse

import jax

from repro import configs
from repro.data import SyntheticTokens
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.workloads import make_optimizer_for
from repro.models.api import build
from repro.train import Trainer, TrainerConfig, build_train_step, init_state


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chatglm3_6b", choices=list(configs.ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    api = build(cfg)
    opt = make_optimizer_for(cfg)
    mesh = make_host_mesh(tp=args.tp)

    def extras(key, n):
        import jax.numpy as jnp
        ex = {}
        if cfg.family == "vlm":
            ex["patch_embeds"] = jax.random.normal(
                key, (n, cfg.num_image_tokens, cfg.d_model),
                jnp.dtype(cfg.dtype))
        if cfg.family == "audio":
            ex["audio"] = jax.random.normal(
                key, (n, cfg.encoder_len, cfg.d_model), jnp.dtype(cfg.dtype))
        return ex

    pipe = SyntheticTokens(vocab=cfg.vocab, seq=args.seq,
                           global_batch=args.batch, seed=args.seed,
                           extras=extras)
    step_fn = build_train_step(api, opt, microbatches=args.microbatches)
    with jax.set_mesh(mesh):
        state = init_state(api, opt, jax.random.PRNGKey(args.seed))
        trainer = Trainer(step_fn, pipe, TrainerConfig(
            total_steps=args.steps, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every))
        state, out = trainer.run(state)
    h = out["loss_history"]
    print(f"[train] {cfg.name}: step {int(state.step)}, "
          f"loss {h[0]:.4f} -> {h[-1]:.4f}, stragglers={len(trainer.stragglers)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
