"""End-to-end distributed training driver.

Trains any assigned architecture with D-SGD over the devices present:

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen3-0.6b --steps 50 --topology stl-fw --budget 3

The mesh is ``(data, model)`` over ``jax.devices()``; by default every
device is one D-SGD node (``--data`` = device count, ``--model`` = 1).
Without ``--full`` the architecture's reduced smoke config runs; with it,
the published config. The learned STL-FW topology is built from the data
pipeline's per-node domain histograms -- exactly the paper's
pre-processing step -- and executed as a Birkhoff ppermute schedule.

``run(argv)`` is the same entry point in-process: it returns the per-step
losses, the setup, the final parameters, the mesh and the schedule.
"""

from __future__ import annotations

import argparse
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core import learn_topology, schedule_from_result, topology as topo
from repro.core.mixing import schedule_from_matrix
from repro.data.tokens import DomainSkewCorpus, TokenBatcher
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_device_mesh
from repro.obs.trace import Tracer
from repro.train.checkpoints import CheckpointManager
from repro.train.lm_trainer import make_train_setup

PARAM_SEED = 0  # the initial parameters are init_params(PRNGKey(PARAM_SEED))


def build_topology(kind: str, Pi: np.ndarray, budget: int, lam: float):
    n = Pi.shape[0]
    if kind == "complete":
        return None  # pmean
    if kind == "ring":
        return schedule_from_matrix(topo.ring(n))
    if kind == "random":
        return schedule_from_matrix(topo.random_d_regular(n, min(budget, n - 1), seed=0))
    if kind == "stl-fw":
        return schedule_from_result(learn_topology(Pi, budget=budget, lam=lam))
    raise ValueError(kind)


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="python -m repro.launch.train")
    ap.add_argument("--arch", default="qwen3-0.6b", choices=list(ARCH_IDS))
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--lr", type=float, default=5e-3)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--per-node-batch", type=int, default=2)
    ap.add_argument("--topology", default="stl-fw",
                    choices=["stl-fw", "random", "ring", "complete"])
    ap.add_argument("--budget", type=int, default=2, help="STL-FW d_max")
    ap.add_argument("--lam", type=float, default=0.1)
    ap.add_argument("--full", action="store_true",
                    help="the published config (default: the smoke config)")
    ap.add_argument("--data", type=int, default=None,
                    help="D-SGD nodes (default: device count / --model)")
    ap.add_argument("--model", type=int, default=1,
                    help="tensor-parallel devices per node")
    ap.add_argument("--ckpt-dir", default=None)
    return ap


def _batch(cfg, toks: np.ndarray, labels: np.ndarray) -> dict:
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
    b, per, _ = toks.shape
    if cfg.arch_type == "vlm":
        batch["image_embeds"] = jnp.zeros(
            (b, per, cfg.vision.num_patches, cfg.d_model), jnp.dtype(cfg.dtype)
        )
    if cfg.arch_type == "audio":
        batch["frames"] = jnp.zeros(
            (b, per, cfg.encoder.num_frames, cfg.d_model), jnp.dtype(cfg.dtype)
        )
        batch["tokens"] = batch["tokens"][..., :448]
        batch["labels"] = batch["labels"][..., :448]
    return batch


def run(argv: Sequence[str] | None = None) -> dict:
    """Train as the command line ``argv`` says; return what the run made.

    Keys: ``losses`` (one float per step), ``setup`` (the ``TrainSetup``),
    ``params`` (final, stacked per node), ``mesh``, ``schedule`` (None for
    the complete graph), ``cfg``, ``batch0`` (the step-0 batch), and the
    host clock's ``compile_s`` (the step's compile), ``step_s`` and
    ``batch_s`` (one entry per step): the durations of the ``train.compile``,
    ``train.step`` and ``train.batch`` spans, which under
    ``jax.profiler.trace`` also sit on the profiler's clock.
    """
    ap = _parser()
    args = ap.parse_args(argv)
    devices = jax.devices()
    print(f"platform {devices[0].platform}  device_kind {devices[0].device_kind}  "
          f"count {len(devices)}", flush=True)
    enable_compile_cache()

    n_dev = len(devices)
    data = args.data if args.data is not None else n_dev // args.model
    if data < 1 or data * args.model != n_dev:
        ap.error(f"--data {data} x --model {args.model} must equal the "
                 f"{n_dev} devices present")
    mesh = make_device_mesh(data, args.model)
    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    n_nodes = mesh.shape["data"]
    print(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size}; mesh data={data} model={args.model}",
          flush=True)

    # Heterogeneous data: one skewed domain mixture per node.
    n_domains = max(4, n_nodes // 2)
    corpus = DomainSkewCorpus(vocab_size=cfg.vocab_size, n_domains=n_domains, seed=0)
    Pi = np.full((n_nodes, n_domains), 0.1 / (n_domains - 1))
    Pi[np.arange(n_nodes), np.arange(n_nodes) % n_domains] = 0.9
    Pi /= Pi.sum(1, keepdims=True)
    batcher = TokenBatcher(corpus, Pi, args.per_node_batch, args.seq_len, seed=1)

    schedule = build_topology(args.topology, Pi, args.budget, args.lam)
    if schedule is not None:
        print(f"topology '{args.topology}': {schedule.n_communication_atoms} "
              f"communication atoms (d_max bound)")

    setup = make_train_setup(cfg, mesh, mode="dsgd", schedule=schedule, lr=args.lr)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), setup.param_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    losses: list[float] = []
    tracer = Tracer(capacity=2 * args.steps + 1)  # holds every span of the run
    step_fn = batch0 = None
    with jax.set_mesh(mesh):
        params = jax.jit(setup.init_params, out_shardings=shardings)(
            jax.random.PRNGKey(PARAM_SEED)
        )
        for t in range(args.steps):
            with tracer.span("train.batch"):
                batch = _batch(cfg, *batcher.next_batch(t))
            if step_fn is None:
                batch0 = batch
                with tracer.span("train.compile"):
                    step_fn = jax.jit(setup.train_step).lower(
                        params, None, batch).compile()
                print(f"compiled the step in "
                      f"{tracer.total_s('train.compile'):.1f}s", flush=True)
            with tracer.span("train.step"):
                params, _, loss = step_fn(params, None, batch)
                losses.append(float(loss))  # waits for the step
            if t % 5 == 0 or t == args.steps - 1:
                step, batch_rec = tracer.spans()[-1], tracer.spans("train.batch")[-1]
                print(f"step {t:4d}  loss {losses[-1]:.4f}  "
                      f"({step.duration_s:.2f}s step, {batch_rec.duration_s:.2f}s batch)",
                      flush=True)
        if ckpt is not None:
            ckpt.save(args.steps, jax.device_get(params))
            print(f"checkpoint written to {args.ckpt_dir}")
    if losses:
        print(f"loss: {losses[0]:.4f} -> {losses[-1]:.4f} over {args.steps} steps")
    return {
        "losses": losses,
        "setup": setup,
        "params": params,
        "mesh": mesh,
        "schedule": schedule,
        "cfg": cfg,
        "batch0": batch0,
        "compile_s": (tracer.total_s("train.compile")
                      if step_fn is not None else None),
        "step_s": [r.duration_s for r in tracer.spans("train.step")],
        "batch_s": [r.duration_s for r in tracer.spans("train.batch")],
    }


def main() -> None:
    run()


if __name__ == "__main__":
    main()
