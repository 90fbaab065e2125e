"""Workload assembly for the dry-run: (arch x shape x mesh) -> jittable step.

``build_workload`` returns the step callable, its abstract inputs
(ShapeDtypeStructs -- nothing is allocated) and in/out shardings, so the
dry-run does::

    jax.jit(fn, in_shardings=..., out_shardings=..., donate_argnums=...)
       .lower(*abstract_inputs).compile()

Workload kinds:
  train    (state, batch)  -> (state, metrics)      full fwd+bwd+optimizer
  prefill  (params, batch, cache) -> (logits, cache)
  decode   (params, token, cache) -> (logits, cache) one token vs seq cache
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.models.api import ModelApi, build
from repro.models.config import ModelConfig
from repro.optim import adafactor, adamw, warmup_cosine
from repro.parallel import specs as S
from repro.train import build_train_step, init_state


def mesh_view(mesh, mode: str):
    """Re-express the SAME physical device set as a different logical mesh.

    The paper's three-mode parallel strategy (C6) at LM scale: the mode IS
    the logical mesh view --

      "2d"  (16,16) data x model          Megatron TP+DP (baseline)
      "dp"  (256,1) pure data parallel    small dense models: TP=16 pays
                                          4 x (B S d) all-reduces/layer for
                                          a model that fits one chip; DP
                                          pays only the gradient reduction
      "tp"  (1,256) pure model parallel   (completeness; huge, latency-bound)

    Multi-pod keeps the leading "pod" axis (cross-pod stays gradient-only).
    """
    import numpy as np

    devices = np.asarray(mesh.devices)
    if "pod" in mesh.axis_names:
        pod = mesh.shape["pod"]
        rest = devices.reshape(pod, -1)
        if mode == "dp":
            shape, names = (pod, rest.shape[1], 1), ("pod", "data", "model")
        elif mode == "tp":
            shape, names = (pod, 1, rest.shape[1]), ("pod", "data", "model")
        else:
            return mesh
        return _mesh_of(devices.reshape(shape), names)
    n = devices.size
    if mode == "dp":
        shape, names = (n, 1), ("data", "model")
    elif mode == "tp":
        shape, names = (1, n), ("data", "model")
    else:
        return mesh
    return _mesh_of(devices.reshape(shape), names)


def _mesh_of(devices, names):
    return jax.sharding.Mesh(
        devices, names, axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def _lm_plan(cfg: ModelConfig, shape: str):
    """Resolve the cached LM workload plan for (arch, run shape).

    The decision itself lives in the ConvPlan layer (``repro.core.plan``
    -- the single planning point for parallel-mode/microbatching policy);
    this module only extracts the scale facts the planner keys on.
    """
    from repro.core.plan import LMWorkloadSpec, plan_lm

    sp = configs.SHAPES[shape]
    return plan_lm(LMWorkloadSpec(
        n_params=float(cfg.n_params()),
        is_moe=cfg.is_moe,
        kind=sp.kind,
        batch=sp.batch,
    ))


def choose_lm_mode(cfg: ModelConfig, shape: str) -> str:
    """C6 analogue: parallel mode by model/workload scale (plan-layer)."""
    return _lm_plan(cfg, shape).parallel_mode


def microbatches_for(cfg: ModelConfig, shape: str) -> int:
    """Gradient-accumulation depth for training shapes (plan-layer)."""
    return _lm_plan(cfg, shape).microbatches


def make_optimizer_for(cfg: ModelConfig):
    sched = warmup_cosine(3e-4, 2000, 100_000)
    if cfg.optimizer == "adafactor":
        return adafactor(sched, weight_decay=0.01)
    # bf16 params -> fp32 master copies; moments in bf16 above 50B params to
    # respect the HBM budget (recorded per arch in EXPERIMENTS.md SSDry-run)
    big = cfg.n_params() > 50e9
    return adamw(sched, weight_decay=0.01,
                 master=cfg.param_dtype != "float32",
                 state_dtype="bfloat16" if big else "float32")


@dataclasses.dataclass
class Workload:
    kind: str
    fn: Callable
    abstract_args: tuple
    in_shardings: tuple
    out_shardings: Any
    donate: tuple[int, ...]
    api: ModelApi
    mesh: Any = None          # the (possibly re-viewed) mesh to lower under


def build_workload(cfg: ModelConfig, shape: str, mesh,
                   parallel_mode: str = "2d") -> Workload:
    if parallel_mode != "2d":
        mesh = mesh_view(mesh, parallel_mode)
    api = build(cfg)
    spec = configs.input_specs(cfg, shape)
    kind = spec["kind"]
    repl = NamedSharding(mesh, P())

    if kind == "train":
        opt = make_optimizer_for(cfg)
        state_abs = jax.eval_shape(
            lambda: init_state(api, opt, jax.random.PRNGKey(0)))
        # microbatch so per-layer remat carries + flash-attn backward
        # residuals fit the HBM budget (8 accumulation steps at B=256).
        # dp mode: the whole global batch is one microbatch (1 row/device)
        # and params go ZeRO-3 over the full mesh.
        mb = 1 if parallel_mode == "dp" else microbatches_for(cfg, shape)
        step = build_train_step(
            api, opt, microbatches=mb,
            accum_dtype="bfloat16" if cfg.n_params() > 50e9 else "float32")
        st_sh = S.state_shardings(
            state_abs, mesh,
            fsdp_params=cfg.fsdp_params or parallel_mode == "dp",
            fsdp_opt=cfg.fsdp_opt)
        b_sh = S.batch_shardings(spec["batch"], mesh)
        metrics_sh = jax.tree_util.tree_map(
            lambda _: repl,
            jax.eval_shape(step, state_abs, spec["batch"])[1])
        return Workload(
            kind="train",
            fn=step,
            abstract_args=(state_abs, spec["batch"]),
            in_shardings=(st_sh, b_sh),
            out_shardings=(st_sh, metrics_sh),
            donate=(0,),
            api=api,
            mesh=mesh,
        )

    params_abs = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
    p_sh = S.params_shardings(params_abs, mesh, fsdp=cfg.fsdp_params)
    long = shape == "long_500k"
    c_sh = S.cache_shardings(spec["cache"], mesh, long=long)

    if kind == "prefill":
        def fn(params, batch, cache):
            return api.prefill(params, batch, cache, long=long)

        b_sh = S.batch_shardings(spec["batch"], mesh)
        logits_sh = jax.tree_util.tree_map(
            lambda _: repl,
            jax.eval_shape(fn, params_abs, spec["batch"], spec["cache"])[0])
        return Workload(
            kind="prefill",
            fn=fn,
            abstract_args=(params_abs, spec["batch"], spec["cache"]),
            in_shardings=(p_sh, b_sh, c_sh),
            out_shardings=(logits_sh, c_sh),
            donate=(2,),
            api=api,
            mesh=mesh,
        )

    # decode
    def fn(params, token, cache):
        return api.decode_step(params, token, cache, long=long)

    tok_sh = S.batch_shardings({"token": spec["token"]}, mesh)["token"]
    logits_sh = jax.tree_util.tree_map(
        lambda _: repl,
        jax.eval_shape(fn, params_abs, spec["token"], spec["cache"])[0])
    return Workload(
        kind="decode",
        fn=fn,
        abstract_args=(params_abs, spec["token"], spec["cache"]),
        in_shardings=(p_sh, tok_sh, c_sh),
        out_shardings=(logits_sh, c_sh),
        donate=(2,),
        api=api,
        mesh=mesh,
    )


# --------------------------- CNN training (SS8) ---------------------------
#
# The CNN counterpart of the LM workloads above, and the workload that
# makes the Winograd backward pass load-bearing: a full train step over a
# ``models.cnn`` network on a mesh runs the forward pipelines AND the
# F(r, m) filter-gradient / rotated-filter dx pipelines under shard_map
# (``train.cnn.build_cnn_train_step``).  Typical entry::
#
#     wl = build_cnn_workload("vgg16", mesh=host_mesh(8))
#     state, metrics = run_cnn_workload(wl, steps=8)


@dataclasses.dataclass
class CNNWorkload:
    kind: str                 # "cnn_train"
    arch: str
    step: Callable            # (state, batch) -> (state, metrics)
    state: Any                # initialized TrainState
    pipeline: Any             # .batch_at(step) -> {"images", "labels"}
    mesh: Any = None


def build_cnn_workload(
    arch: str = "vgg16",
    *,
    mesh=None,
    batch: int = 8,
    hw: int = 32,
    n_classes: int = 10,
    width_mult: float = 0.125,
    algorithm: str = "auto",
    lr: float = 3e-3,
    seed: int = 0,
) -> CNNWorkload:
    """Assemble a trainable CNN workload on the Winograd conv stack.

    ``mesh`` (e.g. ``launch.mesh.host_mesh(8)``) shards every eligible
    conv's forward and backward GEMMs; the image batch is zero-padded to
    the mesh's "data" multiple by the caller if ragged (the serving
    engine's convention).  The reduced defaults (width_mult, 32px) keep a
    host-mesh smoke run in seconds; production scales the same entry.
    """
    from repro.data import SyntheticImages
    from repro.models.cnn import CNN_BUILDERS
    from repro.optim import adamw, warmup_cosine
    from repro.train import build_cnn_train_step, init_cnn_state

    init_fn, forward = CNN_BUILDERS[arch]
    opt = adamw(warmup_cosine(lr, 5, 1000), weight_decay=0.01)
    state = init_cnn_state(init_fn, opt, jax.random.PRNGKey(seed),
                           width_mult=width_mult, n_classes=n_classes)
    step = build_cnn_train_step(forward, opt, algorithm=algorithm, mesh=mesh)
    pipe = SyntheticImages(hw=hw, channels=3, n_classes=n_classes,
                           global_batch=batch, seed=seed)
    return CNNWorkload(kind="cnn_train", arch=arch, step=step, state=state,
                       pipeline=pipe, mesh=mesh)


def run_cnn_workload(wl: CNNWorkload, *, steps: int = 8,
                     donate: bool = True) -> tuple[Any, dict]:
    """Run ``steps`` jitted train steps; returns (state, last metrics +
    loss_history).  The jit cache entry keeps its sharded form, so
    steady-state steps pay no re-partitioning cost.  ``wl.state`` is
    rebound to the final state: with donation the input buffers are
    consumed, so the workload must never keep pointing at them (repeat
    runs continue from where the last one stopped)."""
    fn = jax.jit(wl.step, donate_argnums=(0,) if donate else ())
    state, metrics, history = wl.state, {}, []
    start = int(state.step)
    for i in range(start, start + steps):
        state, metrics = fn(state, wl.pipeline.batch_at(i))
        history.append(float(metrics["loss"]))
    wl.state = state
    return state, {"loss_history": history,
                   **{k: float(v) for k, v in metrics.items()}}


def lower_workload(wl: Workload, mesh=None):
    """jit + lower under the mesh context; returns the Lowered object.

    ``jax.set_mesh`` (not a bare ``with mesh:``) -- only the
    ambient-mesh context makes in-model ``with_sharding_constraint`` calls
    (and the vocab-parallel shard_map) resolve during tracing.
    """
    fn = jax.jit(
        wl.fn,
        in_shardings=wl.in_shardings,
        out_shardings=wl.out_shardings,
        donate_argnums=wl.donate,
    )
    with jax.set_mesh(wl.mesh if wl.mesh is not None else mesh):
        return fn.lower(*wl.abstract_args)
