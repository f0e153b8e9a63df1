"""Training driver: data pipeline -> sharded train loop -> checkpoints,
with preemption safety and straggler telemetry wired in.

CPU-scale usage (see examples/train_100m.py for the end-to-end run):

    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b \
        --reduced --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On a real fleet the same driver runs under `jax.distributed.initialize`
with the production mesh; the dry-run (repro.launch.dryrun) is the
scale-proof for those configurations.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.obs import cli as obs_cli
from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticLMDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.models.frontend import audio_frames, vision_patches
from repro.optim.adamw import AdamWConfig
from repro.runtime import PreemptionGuard, StragglerDetector
from repro.train.state import init_train_state
from repro.train.step import GradSyncConfig, make_train_step


def make_dp_mesh():
    """(pod, data) mesh over every visible device: the hierarchical DP
    topology the collective planner plans for.  Two virtual pods when
    the device count splits evenly, a single pod otherwise."""
    nd = len(jax.devices())
    pod = 2 if nd >= 4 and nd % 2 == 0 else 1
    return make_mesh((pod, nd // pod), ("pod", "data"))


def build_batch(cfg, data_batch, key):
    batch = {"tokens": jnp.asarray(data_batch["tokens"]),
             "labels": jnp.asarray(data_batch["labels"])}
    b, s = batch["tokens"].shape
    if cfg.family == "encdec":
        batch["frames"] = audio_frames(key, cfg, b, s)
    if cfg.frontend == "vision":
        batch["soft_emb"] = vision_patches(key, cfg, b)
    return batch


def install_fabric_topology(spec: str):
    """Parse a ``--fabric`` spec (``pod=slow,data=fast`` or a JSON
    path) and install an engine with those per-axis constants as the
    process default, so every engine-routed collective -- grad sync,
    serve -- is planned against the declared link speeds."""
    from repro.core.model import TPU_V5E_AXIS, parse_fabric_topology
    from repro.collectives.api import set_engine
    from repro.collectives.engine import CollectiveEngine

    topo = parse_fabric_topology(spec)
    engine = CollectiveEngine(fabric=topo)
    set_engine(engine)
    # call sites ask for the stock default fabric; pin the topology
    # engine under that key too, or a spec that overrides `default`
    # would print its topology and then never price anything
    set_engine(engine, fabric=TPU_V5E_AXIS)
    return topo


def run(arch: str, steps: int, batch_size: int, seq_len: int,
        reduced: bool = True, ckpt_dir: str | None = None,
        ckpt_every: int = 50, lr: float = 3e-4, microbatches: int = 1,
        log_every: int = 10, resume: bool = True, dp: bool = False,
        grad_sync_mode: str = "allreduce", fused: bool = False,
        fabric_spec: str | None = None,
        moe_ep: str | None = None, num_experts: int | None = None,
        trace: str | None = None, obs_report: bool = False,
        metrics_out: str | None = None):
    obs_cli.begin(trace, obs_report, metrics_out)
    if fabric_spec:
        topo = install_fabric_topology(fabric_spec)
        print(f"[train] fabric topology: {topo.describe()}")
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    if num_experts is not None or moe_ep is not None:
        import dataclasses
    if num_experts is not None:
        cfg = dataclasses.replace(cfg, num_experts=num_experts)
    if moe_ep is not None:
        if cfg.family != "moe":
            raise SystemExit(f"--moe-ep needs an MoE architecture; "
                             f"{arch} is family={cfg.family!r}")
        cfg = dataclasses.replace(cfg, moe_ep=True,
                                  moe_ep_algorithm=moe_ep)
        print(f"[train] expert-parallel MoE dispatch: "
              f"all_to_all[{moe_ep}]")
    if fused or cfg.fused_tp:
        from repro.models.layers import set_fused_tp
        set_fused_tp(True)
        print("[train] fused matmul+reduce-scatter executor enabled")
    schedule = "wsd" if arch == "minicpm-2b" else "cosine"
    opt_cfg = AdamWConfig(lr=lr, warmup_steps=max(steps // 20, 1),
                          total_steps=steps, schedule=schedule)

    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    state = init_train_state(params)
    data = SyntheticLMDataset(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq_len,
        global_batch=batch_size))

    start_step = 0
    mgr = None
    if ckpt_dir:
        mgr = CheckpointManager(ckpt_dir, keep=2, async_save=True)
        if resume and mgr.committed_steps():
            start_step, state, meta = mgr.restore(state)
            print(f"[train] resumed from step {start_step}")

    mesh = None
    grad_sync = None
    batch_sharding = None
    if dp:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.sharding.rules import grad_sync_axes_for_mesh
        mesh = make_dp_mesh()
        axes = grad_sync_axes_for_mesh(mesh)
        grad_sync = GradSyncConfig(mesh=mesh, axes=axes,
                                   mode=grad_sync_mode, fused=fused)
        n_dp = 1
        for a in axes:
            n_dp *= mesh.shape[a]
        if axes and batch_size % n_dp == 0:
            batch_sharding = NamedSharding(
                mesh, P(axes if len(axes) > 1 else axes[0]))
        elif n_dp > 1:
            print(f"[train] WARNING: batch {batch_size} not divisible "
                  f"by DP world {n_dp}; batch stays replicated (no DP "
                  f"speedup, sync path still exercised)")
        print(f"[train] dp mesh {dict(mesh.shape)} grad-sync axes "
              f"{axes} mode={grad_sync_mode}"
              + (" fused" if fused else ""))
    step_fn = jax.jit(make_train_step(cfg, opt_cfg,
                                      microbatches=microbatches,
                                      grad_sync=grad_sync))
    guard = PreemptionGuard(install=True)
    stragglers = StragglerDetector()
    host = f"host{jax.process_index()}"
    losses = []
    t_start = time.time()
    for step in range(start_step, steps):
        t0 = time.time()
        batch = build_batch(cfg, data.batch(step), jax.random.fold_in(key,
                                                                      step))
        if batch_sharding is not None:
            batch = {k: jax.device_put(v, batch_sharding)
                     for k, v in batch.items()}
        if mesh is not None:
            with jax.set_mesh(mesh):
                state, metrics = step_fn(state, batch)
        else:
            state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        stragglers.record(host, time.time() - t0)
        if step % log_every == 0 or step == steps - 1:
            print(f"[train] step={step} loss={loss:.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"dt={time.time() - t0:.2f}s", flush=True)
        if mgr and (step + 1) % ckpt_every == 0:
            mgr.save(step + 1, state, metadata={"loss": loss},
                     block=False)
        if guard.should_stop:
            print("[train] preemption requested: checkpointing and "
                  "exiting")
            if mgr:
                mgr.save(step + 1, state, metadata={"loss": loss})
            break
    if mgr:
        mgr.save(steps, state, metadata={"loss": losses[-1]})
        mgr.wait()
    print(f"[train] done: {len(losses)} steps in "
          f"{time.time() - t_start:.1f}s; "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    if mesh is not None:
        with jax.set_mesh(mesh):
            obs_cli.finish(trace, obs_report, metrics_out, mesh=mesh,
                           label="train")
    else:
        obs_cli.finish(trace, obs_report, metrics_out, label="train")
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--dp", action="store_true",
                    help="hierarchical (pod, data) DP over all devices; "
                         "gradient sync through the collective planner")
    ap.add_argument("--grad-sync", choices=("allreduce", "fsdp"),
                    default="allreduce",
                    help="engine sync shape under --dp: bucketed "
                         "allreduce or the FSDP RS/AG pair")
    ap.add_argument("--fused", action="store_true",
                    help="route the grad sync (and TP projections, "
                         "when a model axis exists) through the "
                         "engine's fused matmul+reduce-scatter "
                         "executor (kernels/fused_matmul_rs.py)")
    ap.add_argument("--fabric", default=None, metavar="SPEC",
                    help="heterogeneous fabric topology: "
                         "'pod=slow,data=fast' (presets or link_bw "
                         "multipliers) or a path to a JSON topology "
                         "file; the planner prices each mesh axis "
                         "with its declared link constants")
    ap.add_argument("--moe-ep", nargs="?", const="auto", default=None,
                    metavar="ALGO",
                    help="route MoE expert dispatch/combine through "
                         "explicit all-to-all (models/moe_ep.py): "
                         "'lax' = bare single-shot baseline, else an "
                         "engine algorithm or plan shape ('auto', "
                         "'hierarchical', 'ring', ...; default auto)")
    ap.add_argument("--experts", type=int, default=None,
                    help="override num_experts (e.g. to tile the "
                         "8-virtual-device EP world under --reduced)")
    obs_cli.add_obs_args(ap)
    args = ap.parse_args()
    print(f"[train] compile cache: {enable_compile_cache()}")
    run(args.arch, args.steps, args.batch, args.seq, reduced=args.reduced,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every, lr=args.lr,
        microbatches=args.microbatches, dp=args.dp,
        grad_sync_mode=args.grad_sync, fused=args.fused,
        fabric_spec=args.fabric,
        moe_ep=args.moe_ep, num_experts=args.experts,
        trace=args.trace, obs_report=args.obs_report,
        metrics_out=args.metrics_out)


if __name__ == "__main__":
    main()
