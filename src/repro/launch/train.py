"""Training driver: sharded train step factory + end-to-end loop.

The step factory builds one jitted train step for (arch config, mesh, rules):

  * params/moments sharded by the logical-axis rules (FSDP over 'data',
    TP/EP over 'model', pure DP over 'pod')
  * batch sharded over ('pod', 'data')
  * optional cross-pod gradient compression: the loss+grad computation runs
    inside a *partially-manual* shard_map (manual over 'pod' only), local
    grads are reduced over the pod ring with bf16/int8 payloads
    (optim/grad_compress), with error-feedback residual carried in the state
  * optional in-graph in-situ hooks (HYBRID mode): the spectral-lossy device
    stage for selected state leaves is compiled into the step, so the step's
    outputs already contain the reduced representation (the NEKO pattern)

The loop (main) wires the substrate together: data prefetcher, in-situ
engine (analytics + checkpointing), straggler monitor, restore-on-start.
Runs on CPU for smoke configs; the same code lowers for the production mesh
in the dry-run.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import optim
from repro.configs import base as configs
from repro.distributed import sharding
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models import params as P_lib
from repro.models import transformer
from repro.optim import grad_compress

PyTree = Any


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------

def state_spec(cfg: configs.ModelConfig, *, master: bool = False,
               ef_pods: int = 0) -> dict:
    """Abstract (ShapeDtypeStruct) training state for lowering."""
    pspec = transformer.param_spec(cfg)
    params = P_lib.abstract(pspec)
    mdt = jnp.bfloat16
    mom = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, mdt), params)
    state = {
        "params": params,
        "mu": mom,
        "nu": mom,
        "count": jax.ShapeDtypeStruct((), jnp.int32),
    }
    if master:
        state["master"] = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params)
    if ef_pods:
        # per-pod local residual: leading pod axis
        state["ef"] = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((ef_pods,) + s.shape,
                                           jnp.bfloat16), params)
    return state


def init_state(cfg: configs.ModelConfig, rng, opt_cfg: optim.AdamWConfig,
               *, ef_residual: bool = False) -> dict:
    pspec = transformer.param_spec(cfg)
    params = P_lib.materialize(rng, pspec)
    ostate = optim.init(params, opt_cfg)
    state = {"params": params, "mu": ostate.mu, "nu": ostate.nu,
             "count": ostate.count}
    if opt_cfg.master_weights:
        state["master"] = ostate.master
    if ef_residual:
        state["ef"] = grad_compress.ef_init(params)
    return state


def state_shardings(cfg: configs.ModelConfig, mesh: Mesh,
                    rules=None, *, master: bool = False,
                    ef_residual: bool = False) -> dict:
    rules = rules if rules is not None else sharding.DEFAULT_RULES
    pspec = transformer.param_spec(cfg)
    axes = P_lib.logical_axes(pspec)
    abstract = P_lib.abstract(pspec)
    pspecs = sharding.tree_partition_specs(abstract, axes, rules, mesh)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    out = {
        "params": pshard,
        "mu": pshard,
        "nu": pshard,
        "count": NamedSharding(mesh, P()),
    }
    if master:
        out["master"] = pshard
    if ef_residual:
        out["ef"] = jax.tree.map(
            lambda s: NamedSharding(mesh, P("pod", *tuple(s))), pspecs)
    return out


def batch_shardings(cfg: configs.ModelConfig, shape: configs.ShapeConfig,
                    mesh: Mesh, rules=None) -> dict:
    extra = sharding.batch_over_model(rules) if rules is not None else False
    bspec = sharding.batch_spec(mesh, shape.global_batch, extra_model=extra)
    out = {"tokens": NamedSharding(mesh, bspec),
           "labels": NamedSharding(mesh, bspec)}
    if cfg.frontend:
        out["prefix"] = NamedSharding(mesh, bspec)
    return out


def batch_abstract(cfg: configs.ModelConfig, shape: configs.ShapeConfig) -> dict:
    b, s = shape.global_batch, shape.seq_len
    out = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
           "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    if cfg.frontend:
        out["prefix"] = jax.ShapeDtypeStruct(
            (b, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
    return out


# ---------------------------------------------------------------------------
# step factory
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StepConfig:
    opt: optim.AdamWConfig = dataclasses.field(
        default_factory=optim.AdamWConfig)
    grad_compress: str = "none"      # none | bf16 | int8 (cross-pod wire)
    lr_peak: float = 3e-4
    lr_warmup: int = 100
    lr_total: int = 10000
    remat: bool = True


def make_train_step(cfg: configs.ModelConfig, mesh: Mesh,
                    step_cfg: StepConfig, *, rules=None,
                    shape: Optional[configs.ShapeConfig] = None) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics) (to be jitted)."""
    rules = rules if rules is not None else sharding.DEFAULT_RULES
    n_pods = grad_compress.pod_size(mesh, "pod")
    use_pod_ring = step_cfg.grad_compress != "none" and n_pods > 1
    # batch activation constraint on dim 0: dp axes (+ 'model' for pure_dp)
    gb = shape.global_batch if shape is not None else 1 << 30
    bspec = sharding.batch_spec(mesh, gb,
                                extra_model=sharding.batch_over_model(rules))

    def local_grads(params, batch, bspec_):
        loss_fn = lambda p, b: transformer.train_loss(p, cfg, b, bspec=bspec_)
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        return loss, grads

    def _pod_ring_grads(params, batch, state):
        """Compressed cross-pod gradient path (manual over 'pod' only).

        XLA's SPMD partitioner cannot partition token-embedding *gathers*
        inside a partially-manual region (hard CHECK), so the embedding
        lookups are hoisted OUT and vjp-split: their cotangents flow back
        through the auto context (exact scatter-reduction over all axes),
        while every dense gradient rides the compressed pod ring. The loss
        also switches to the gather-free cross-entropy.
        """
        use_ef = "ef" in state
        emb_table = params["embed"]["embedding"]

        def gather_stage(tbl):
            outs = {"h0": jnp.take(tbl, batch["tokens"], axis=0)}
            if cfg.family == "moe" and cfg.mtp_weight > 0:
                outs["mtp_cur"] = jnp.take(tbl, batch["tokens"], axis=0)
                outs["mtp_emb"] = jnp.take(tbl, batch["labels"], axis=0)
            return outs

        gathered, gather_vjp = jax.vjp(gather_stage, emb_table)

        # pod-major reshape: a dim cannot mix manual+auto axes in one spec
        # entry, so 'pod' gets its own leading axis.
        def to_pod_major(x):
            x = x.reshape((n_pods, x.shape[0] // n_pods) + x.shape[1:])
            return jax.lax.with_sharding_constraint(x, P("pod", "data"))

        batch_pm = jax.tree.map(to_pod_major, batch)
        gathered_pm = jax.tree.map(to_pod_major, gathered)
        pspec_none = jax.tree.map(lambda _: P(), params)
        pm_specs = jax.tree.map(lambda _: P("pod"), batch_pm)
        g_specs = jax.tree.map(lambda _: P("pod"), gathered_pm)
        ef_specs = (jax.tree.map(lambda _: P("pod"), params)
                    if use_ef else P())

        def pod_local(params_, batch_, gathered_, ef_):
            batch_ = jax.tree.map(lambda x: x[0], batch_)
            gathered_ = jax.tree.map(lambda x: x[0], gathered_)

            def loss_fn(p, g):
                mtp_pre = ((g["mtp_cur"], g["mtp_emb"])
                           if "mtp_cur" in g else None)
                return transformer.train_loss(
                    p, cfg, batch_, bspec=P("data"), h0=g["h0"],
                    mtp_pre=mtp_pre, gather_free=True)

            loss_, (grads_, dgath_) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(params_, gathered_)
            if use_ef:
                ef_ = jax.tree.map(lambda e: e[0], ef_)
                grads_ = grad_compress.ef_pre(grads_, ef_)
            reduced = grad_compress.tree_reduce(
                grads_, method=step_cfg.grad_compress, axis="pod", n=n_pods)
            new_ef_ = (jax.tree.map(
                lambda e: e[None], grad_compress.ef_post(grads_, reduced))
                if use_ef else jnp.zeros((1,), jnp.int32))
            dgath_ = jax.tree.map(lambda x: x[None], dgath_)
            return jax.lax.pmean(loss_, "pod"), reduced, dgath_, new_ef_

        sm = sharding.shard_map(
            pod_local, mesh,
            in_specs=(pspec_none, pm_specs, g_specs, ef_specs),
            out_specs=(P(), pspec_none, g_specs,
                       ef_specs if use_ef else P("pod")),
            axis_names={"pod"}, check_vma=False)
        loss, grads, dgath_pm, new_ef = sm(
            params, batch_pm, gathered_pm,
            state["ef"] if use_ef else jnp.zeros((), jnp.int32))
        # embedding-gather cotangents: back through the auto context (the
        # scatter-add all-reduces exactly over pod+data — uncompressed)
        dgath = jax.tree.map(
            lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]),
            dgath_pm)
        # mean over pods for the gather path (ring already averaged the rest)
        dgath = jax.tree.map(lambda x: x / n_pods, dgath)
        demb = gather_vjp(dgath)[0]
        g_emb = grads["embed"]["embedding"]
        grads["embed"]["embedding"] = (g_emb + demb).astype(g_emb.dtype)
        if not use_ef:
            new_ef = None
        return loss, grads, new_ef

    def train_step(state, batch):
        params = state["params"]
        if use_pod_ring:
            loss, grads, new_ef = _pod_ring_grads(params, batch, state)
        else:
            loss, grads = local_grads(params, batch, bspec)
            new_ef = state.get("ef")

        lr = optim.schedules.warmup_cosine(
            state["count"], peak=step_cfg.lr_peak, warmup=step_cfg.lr_warmup,
            total=step_cfg.lr_total)
        ostate = optim.AdamWState(state["count"], state["mu"], state["nu"],
                                  state.get("master"))
        new_params, new_ostate = optim.update(grads, ostate, params,
                                              step_cfg.opt, lr=lr)
        new_state = dict(state)
        new_state.update(params=new_params, mu=new_ostate.mu,
                         nu=new_ostate.nu, count=new_ostate.count)
        if new_ostate.master is not None:
            new_state["master"] = new_ostate.master
        if new_ef is not None:
            new_state["ef"] = new_ef
        metrics = {"loss": loss.astype(jnp.float32),
                   "grad_norm": optim.adamw.global_norm(grads),
                   "lr": lr}
        return new_state, metrics

    return train_step


def jit_train_step(cfg, mesh, step_cfg: StepConfig, shape, *, rules=None,
                   donate: bool = True):
    """Jitted + sharded train step and the (state, batch) shardings."""
    rules = rules if rules is not None else sharding.DEFAULT_RULES
    ef = step_cfg.grad_compress == "int8" and "pod" in mesh.axis_names
    st_sh = state_shardings(cfg, mesh, rules,
                            master=step_cfg.opt.master_weights,
                            ef_residual=ef)
    b_sh = batch_shardings(cfg, shape, mesh, rules)
    fn = make_train_step(cfg, mesh, step_cfg, rules=rules, shape=shape)
    jitted = jax.jit(
        fn,
        in_shardings=(st_sh, b_sh),
        out_shardings=(st_sh, NamedSharding(mesh, P())),
        donate_argnums=(0,) if donate else ())
    return jitted, st_sh, b_sh, ef


# ---------------------------------------------------------------------------
# end-to-end loop (smoke-scale on CPU; same code path as production)
# ---------------------------------------------------------------------------

def default_train_plan(*, insitu_mode: str = "async",
                       ckpt_dir: Optional[str] = None, ckpt_every: int = 20,
                       analytics_every: int = 10, p_i: int = 2,
                       fault: bool = False,
                       fault_hosts: Optional[list] = None,
                       fault_grace_s: float = 30.0) -> dict:
    """The training loop's declarative in-situ plan, in plain-dict form.

    Two streams: ``grads`` (per-step gradient/param summaries) and
    ``train_state`` (the full checkpointable state). ``fault=True`` adds a
    third, ``health`` (per-step heartbeat + step time), bound to the
    ``fault`` preset — failed-host detection and straggler mitigation run
    on it. Callers can load the same shape from TOML/JSON and pass it to
    ``train_loop(plan=...)``.
    """
    plan: dict = {
        "streams": ["grads", "train_state"],
        "workers": p_i,
        "tasks": {
            "analytics": {"stream": "grads", "preset": "grad_health",
                          "every": analytics_every,
                          "placement": insitu_mode},
        },
    }
    if ckpt_dir:
        plan["tasks"]["checkpoint"] = {
            "stream": "train_state", "preset": "checkpoint",
            "every": ckpt_every, "placement": insitu_mode,
            "options": {"directory": ckpt_dir},
        }
    if fault:
        # sync + every=1: heartbeats must not be shed by backpressure, and
        # mitigation decisions should land on the step that triggered them
        plan["streams"].append("health")
        plan["tasks"]["fault"] = {
            "stream": "health", "preset": "fault", "every": 1,
            "placement": "sync", "pipelined": False,
            "options": {"hosts": list(fault_hosts or [0]),
                        "grace_s": fault_grace_s},
        }
    return plan


def train_loop(arch: str, *, steps: int = 50, smoke: bool = True,
               shape: Optional[configs.ShapeConfig] = None,
               insitu_mode: str = "async", ckpt_dir: Optional[str] = None,
               ckpt_every: int = 20, seed: int = 0,
               analytics_every: int = 10, p_i: int = 2,
               plan: Optional[Any] = None,
               sink_faults: Optional[dict] = None,
               on_session: Optional[Callable[[Any], None]] = None,
               log: Callable[[str], None] = print) -> dict:
    """End-to-end training with the in-situ stack declared as a plan.

    All in-situ work — analytics and checkpointing — is one
    :class:`~repro.core.session.InSituPlan` driven through a single
    :class:`~repro.core.session.Session`; the loop's only in-situ calls are
    ``session.emit``. Pass ``plan`` (an ``InSituPlan`` or its dict form) to
    replace the default workflow wholesale; the legacy kwargs
    (``insitu_mode``/``ckpt_every``/``analytics_every``) parameterize the
    default plan. ``sink_faults`` maps task names to fault hooks installed
    via ``PipelineRuntime.inject_sink_fault`` (transient-failure drills).
    ``on_session`` runs once with the live session before the first step
    (e.g. to grab a task's transport sink for a network-fault drill).
    ``shape`` sets sequence length and global batch (default: the smoke
    shape, or the production-mesh ``train_4k`` when ``smoke=False``).
    Returns the per-step losses and host-clock step times (each ends in
    the loss's device-to-host read), the final state, and the session's
    telemetry and reports.
    """
    from repro.core import InSituPlan, Session, Telemetry
    from repro.data.pipeline import Prefetcher, batch_spec_for
    from repro.distributed.fault import StragglerMonitor

    cfg = configs.get(arch, smoke=smoke)
    if shape is None:
        shape = configs.SMOKE_SHAPE if smoke else configs.SHAPES["train_4k"]
    mesh = mesh_lib.make_mesh((1, 1), ("data", "model"))
    step_cfg = StepConfig()
    tm = Telemetry()

    if plan is None:
        plan = default_train_plan(
            insitu_mode=insitu_mode, ckpt_dir=ckpt_dir,
            ckpt_every=ckpt_every, analytics_every=analytics_every, p_i=p_i)
    if not isinstance(plan, InSituPlan):
        plan = InSituPlan.from_dict(plan)

    with sharding.mesh_context(mesh):
        state = init_state(cfg, jax.random.PRNGKey(seed), step_cfg.opt)
        jitted, st_sh, b_sh, _ = jit_train_step(cfg, mesh, step_cfg, shape,
                                                donate=False)

        # ONE session: analytics and checkpointing share the staging ring
        # and the p_i worker pool (the paper's single p_o/p_i split).
        with Session(plan, telemetry=tm, raise_on_error=True) as session:
            for task_name, hook in (sink_faults or {}).items():
                session.runtime.inject_sink_fault(task_name, hook)
            if on_session is not None:
                on_session(session)
            # record the mesh geometry with every save so a later
            # restore(elastic=True) can plan the remesh from the manifest
            session.set_checkpoint_meta(mesh=mesh)
            if session.latest_checkpoint_step() is not None:
                start, state = session.restore(state)
                log(f"resumed from step {start}")
            # jit does not reshard a committed argument to its in_shardings:
            # place the fresh or restored state on the step's layout
            state = jax.device_put(state, st_sh)

            pf = Prefetcher(batch_spec_for(cfg, shape), depth=2,
                            telemetry=tm)
            mon = StragglerMonitor()
            losses, step_times = [], []
            for i in range(steps):
                batch = jax.device_put(next(pf), b_sh)
                t0 = time.perf_counter()
                with session.step_span(i):
                    state, metrics = jitted(state, batch)
                    loss = float(metrics["loss"])
                step_s = time.perf_counter() - t0
                mon.observe(0, step_s)
                losses.append(loss)
                step_times.append(step_s)
                if "health" in session.streams:
                    # single-process loop: host 0's beat + step time; a
                    # multi-host launcher emits {"hosts": {h: s}} instead
                    session.emit("health", i, {"host": 0, "step_s": step_s})
                # a custom plan may declare only a subset of the default
                # streams — offer each payload only where declared
                if "grads" in session.streams:
                    params_now = state["params"]
                    session.emit("grads", i, lambda p=params_now: {
                        "params": np.asarray(
                            jax.tree.leaves(p)[0].astype(jnp.float32))})
                if "train_state" in session.streams:
                    session.emit("train_state", i, lambda s=state: s)
                if i % 10 == 0:
                    log(f"step {i} loss {loss:.4f} "
                        f"lr {float(metrics['lr']):.2e}")
            pf.close()
    n_analytics = sum(1 for r in session.results if r.task == "analytics")
    return {"losses": losses, "step_s": step_times, "state": state,
            "telemetry": tm,
            "insitu_results": n_analytics,
            "session_report": session.report(),
            "straggler_report": mon.report()}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m",
                    choices=list(configs.ARCH_IDS))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--insitu", default="async",
                    choices=["sync", "async", "hybrid"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--full", action="store_true",
                    help="full (non-smoke) config at its published widths")
    ap.add_argument("--seq-len", type=int, default=None,
                    help="sequence length (with --global-batch; default: "
                         "the smoke shape, or train_4k with --full)")
    ap.add_argument("--global-batch", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()
    shape = None
    if args.seq_len or args.global_batch:
        if not (args.seq_len and args.global_batch):
            ap.error("--seq-len and --global-batch go together")
        shape = configs.ShapeConfig("cli", "train", args.seq_len,
                                    args.global_batch)
    out = train_loop(args.arch, steps=args.steps, smoke=not args.full,
                     shape=shape, insitu_mode=args.insitu,
                     ckpt_dir=args.ckpt_dir)
    print("final loss:", out["losses"][-1])
    print("in-situ results:", out["insitu_results"])


if __name__ == "__main__":
    main()
