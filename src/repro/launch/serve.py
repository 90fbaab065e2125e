"""Serving CLI driver: batched generation with the ServeEngine.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6_1_6b --smoke \\
      --batch 4 --prompt-len 32 --new-tokens 32

``--continuous N`` drives a mixed-arrival stream instead: N requests with
seeded Poisson arrivals and mixed generation lengths run through the
continuous-batching scheduler (slot pool = ``--batch``), and the same
schedule through the uniform static-batching baseline for comparison.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.api import build
from repro.serve import (ContinuousBatchingScheduler, ServeEngine,
                         poisson_schedule, run_uniform_batches)


def _run_continuous(engine, cfg, args) -> None:
    reqs = poisson_schedule(
        args.continuous, cfg.vocab, prompt_len=args.prompt_len,
        min_new=max(1, args.new_tokens // 8), max_new=args.new_tokens,
        temperature=args.temperature, seed=args.seed)
    print(f"[serve] {cfg.name}: {args.continuous} mixed-arrival requests, "
          f"{args.batch} slots, temperature {args.temperature}")
    if args.temperature == 0.0:
        t0 = time.perf_counter()
        uni = run_uniform_batches(engine, reqs, slots=args.batch)
        uni_wall = time.perf_counter() - t0
        print(f"[serve]   uniform    : {uni['useful_tokens']} tokens / "
              f"{uni['decode_steps']} decode steps "
              f"({uni['useful_tokens']/max(uni['decode_seconds'],1e-12):.1f} "
              f"tok/s decode; wall {uni_wall:.2f}s incl. compile)")
    else:
        print("[serve]   uniform    : skipped (the static baseline is "
              "greedy-only)")
    sched = ContinuousBatchingScheduler(engine, slots=args.batch)
    t0 = time.perf_counter()
    done = sched.run(reqs)
    cont_wall = time.perf_counter() - t0
    lat = [done[r.rid].latency_steps for r in reqs]
    print(f"[serve]   continuous : {sched.useful_tokens} tokens / "
          f"{sched.decode_steps} decode steps "
          f"({sched.useful_tokens/max(sched.decode_seconds,1e-12):.1f} tok/s "
          f"decode; wall {cont_wall:.2f}s; mean latency "
          f"{np.mean(lat):.1f} steps)")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chatglm3_6b", choices=list(configs.ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--continuous", type=int, default=0, metavar="N",
                    help="serve N mixed-arrival requests through the "
                         "continuous-batching scheduler (vs the uniform "
                         "baseline) instead of one uniform batch")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    api = build(cfg)
    mesh = make_host_mesh(tp=args.tp)
    with jax.set_mesh(mesh):
        params = api.init(jax.random.PRNGKey(0))
        engine = ServeEngine(api, params,
                             max_len=args.prompt_len + args.new_tokens)
        if args.continuous:
            _run_continuous(engine, cfg, args)
            return 0
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab)
        extras = {}
        if cfg.family == "audio":
            extras["audio"] = jax.random.normal(
                jax.random.PRNGKey(2),
                (args.batch, cfg.encoder_len, cfg.d_model), jnp.dtype(cfg.dtype))
        t0 = time.perf_counter()
        out = engine.generate(prompts, max_new_tokens=args.new_tokens,
                              temperature=args.temperature, extras=extras)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
    n_tok = args.batch * args.new_tokens
    print(f"[serve] {cfg.name}: generated {n_tok} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s incl. compile)")
    print("[serve] sample:", out[0][:16].tolist())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
