"""Smoke run of the D-SGD trainer on a TPU.

    python chip_smoke.py               # one chip: phases (a) and (b)
    python chip_smoke.py --four-chips  # four chips: phase (c) only

(a) The launcher, ``repro.launch.train.run``, trains qwen3-0.6b at its
    published widths for 3 steps as one D-SGD node (STL-FW topology,
    per-node batch 1 x 2048). Every loss must be finite. A plain float32
    evaluation of the same batch on the same initial parameters is the
    reference: the step-0 loss must lie within 1e-4 relative of it (far
    inside 2%).
(b) The paper's gossip path: STL-FW learns a budget-5 topology for 100
    label-skewed nodes, and ``run_classification`` mixes through the Pallas
    ``gossip_schedule`` kernel. The compiled rollout must hold the kernel
    (``tpu_custom_call``), and its losses must match the XLA gather path to
    float32 tolerance. The kernel's bfloat16 path (the tile widened into a
    float32 scratch) must match the ``gossip_schedule_ref`` oracle to one
    bfloat16 rounding on a (100, 32768) stack.
(c) ``--four-chips``: qwen3-0.6b as four nodes, one per chip, mixing by the
    learned budget-2 schedule as ``ppermute``s over ICI. Each node's shard of
    every parameter must sit on its own chip. The final parameters plus
    seeded per-node noise of their own scale (every node starts from one
    init, so unperturbed rows are nearly equal and any W leaves them as they
    are) are mixed; the result must match the dense ``W @ theta`` computed in
    float32 on the host, to bfloat16 tolerance. Two controls must fail that
    bound on every leaf: the unmixed input, and the same schedule with each
    permutation shifted by one node, run through the same ppermutes. Rows
    are 512 tokens here: placement and mixing do not depend on the sequence
    length, and the host draws each token row at full vocabulary width
    (about 15 s per 2048 tokens).

The process exits non-zero, with no result line, when JAX finds no TPU or
any check fails. Times are host-clock times of a smoke run, not a
benchmark. The last line of standard output is the JSON result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import learn_topology  # noqa: E402
from repro.core.mixing import BirkhoffSchedule, schedule_from_result  # noqa: E402
from repro.data.partition import shard_partition  # noqa: E402
from repro.data.synthetic import gaussian_blobs  # noqa: E402
from repro.kernels.gossip_mix import gossip_schedule  # noqa: E402
from repro.kernels.gossip_mix.gossip_schedule import DEFAULT_BLOCK_P  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.train import PARAM_SEED, run  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.train.lm_trainer import gossip_fn  # noqa: E402
from repro.train.trainer import run_classification  # noqa: E402

QWEN3_FULL = ["--arch", "qwen3-0.6b", "--full", "--steps", "3",
              "--per-node-batch", "1", "--topology", "stl-fw", "--budget", "2"]
NOISE_SEED = 7
IR_DIR = os.path.join(HERE, "build", "chip_smoke_ir")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def peak_bytes() -> int:
    return max(d.memory_stats()["peak_bytes_in_use"] for d in jax.local_devices())


def report_run(tag: str, out: dict) -> None:
    print(f"{tag}: compile {out['compile_s']:.2f}s; step seconds (smoke, not a "
          f"benchmark) {[round(s, 3) for s in out['step_s']]}; host batch "
          f"seconds {[round(s, 2) for s in out['batch_s']]}; "
          f"peak_bytes_in_use {peak_bytes()}", flush=True)


def check_qwen3(out: dict, n_nodes: int) -> None:
    cfg = out["cfg"]
    check((cfg.num_layers, cfg.d_model, cfg.vocab_size) == (28, 1024, 151936),
          f"qwen3-0.6b is not at published widths: {cfg}")
    check(out["setup"].n_nodes == n_nodes,
          f"{out['setup'].n_nodes} D-SGD nodes, expected {n_nodes}")
    check(len(out["losses"]) == 3 and bool(np.isfinite(out["losses"]).all()),
          f"losses not 3 finite values: {out['losses']}")


def phase_trainer() -> None:
    out = run(QWEN3_FULL + ["--seq-len", "2048"])
    check_qwen3(out, n_nodes=1)
    report_run("phase a", out)

    # the plain float32 reference: same initial parameters, same batch,
    # float32 matmuls (the TPU's default takes bfloat16 passes)
    cfg32 = dataclasses.replace(out["cfg"], dtype="float32")
    params0 = jax.jit(out["setup"].init_params)(jax.random.PRNGKey(PARAM_SEED))
    params0 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params0)
    loss32 = jax.jit(
        lambda p, b: registry.loss_fn(p, cfg32, b, impl="xla")[0]
    )
    batch0 = out["batch0"]
    n = out["setup"].n_nodes
    with jax.default_matmul_precision("highest"):
        ref = float(np.mean([
            loss32(jax.tree_util.tree_map(lambda x: x[i], params0),
                   {k: v[i] for k, v in batch0.items()})
            for i in range(n)
        ]))
    rel = abs(out["losses"][0] - ref) / abs(ref)
    print(f"phase a: losses {out['losses']}; step-0 loss {out['losses'][0]:.6f} "
          f"vs float32 reference {ref:.6f} (relative difference {rel:.3e})",
          flush=True)
    # 1e-4, not 2%: at random init the loss sits near ln V, and 2% is
    # about the whole excess over a uniform prediction
    check(rel <= 1e-4, f"step-0 loss off the float32 reference by {rel:.3e}")


def phase_gossip_kernel() -> None:
    n, k, dim, budget = 100, 10, 256, 5
    X, y = gaussian_blobs(n_samples=10000, num_classes=k, dim=dim, seed=0)
    idx, Pi = shard_partition(y, n, shards_per_node=2, seed=0)
    schedule = schedule_from_result(learn_topology(Pi, budget=budget, lam=0.1))
    p_node = dim * k + k  # the linear classifier: above the kernel's 2048 tile
    print(f"phase b: n={n}, K={k}, budget {budget}: {schedule.n_atoms} atoms; "
          f"P={p_node}", flush=True)

    def losses(use_kernel: bool) -> tuple[np.ndarray, float]:
        tic = time.perf_counter()
        # float32 matmuls on both sides, so that only the mixing differs
        with jax.default_matmul_precision("highest"):
            log = run_classification(
                X, y, idx, None, model="linear", steps=10, batch_size=32,
                lr=0.1, seed=0, schedule=schedule, transport="schedule",
                use_kernel=use_kernel,
            )
        return np.asarray(log.column("loss")), time.perf_counter() - tic

    shutil.rmtree(IR_DIR, ignore_errors=True)
    jax.config.update("jax_dump_ir_to", IR_DIR)
    try:
        a, kernel_s = losses(use_kernel=True)
    finally:
        jax.config.update("jax_dump_ir_to", "")
    rollouts = [f for f in os.listdir(IR_DIR) if "jit_roll" in f]
    check(len(rollouts) == 1, f"expected one compiled rollout, found {rollouts}")
    with open(os.path.join(IR_DIR, rollouts[0])) as f:
        has_kernel = "tpu_custom_call" in f.read()
    shutil.rmtree(IR_DIR)
    check(has_kernel, "the compiled rollout holds no tpu_custom_call")

    b, plain_s = losses(use_kernel=False)
    diff = float(np.max(np.abs(a - b)))
    print(f"phase b: tpu_custom_call in the compiled rollout; kernel losses "
          f"{a.tolist()}; max |kernel - xla| {diff:.3e}; run seconds incl. "
          f"compile: kernel {kernel_s:.2f}, xla {plain_s:.2f}; "
          f"peak_bytes_in_use {peak_bytes()}", flush=True)
    check(bool(np.isfinite(a).all()), "kernel losses not finite")
    check(bool(np.allclose(a, b, rtol=1e-5, atol=1e-6)),
          "kernel losses differ from the XLA path beyond float32 tolerance")

    # the bfloat16 kernel path against the oracle, at P above the tile
    coeffs, perms = schedule.coeff_array(), schedule.perm_array()
    theta = jax.random.normal(jax.random.PRNGKey(NOISE_SEED),
                              (n, 16 * DEFAULT_BLOCK_P), jnp.bfloat16)
    mix = jax.jit(lambda t: gossip_schedule(t, coeffs, perms)).lower(theta).compile()
    check("tpu_custom_call" in mix.as_text(),
          "the bfloat16 gossip_schedule program holds no tpu_custom_call")
    got = np.asarray(mix(theta), np.float32)
    want = np.asarray(gossip_schedule(theta, coeffs, perms, use_ref=True), np.float32)
    x = np.abs(np.asarray(theta, np.float32))
    # both accumulate in float32 and round once to bfloat16, each by at
    # most the unit roundoff 2^-8 relative to sum_l gamma_l |theta[perm_l]|
    bound = 2 * 2.0**-8 * sum(c * x[p] for c, p in zip(coeffs, perms))
    diff = np.abs(got - want)
    print(f"phase b: bfloat16 kernel at n={n}, P={theta.shape[1]}: "
          f"tpu_custom_call; max |kernel - oracle| {diff.max():.3e}, "
          f"{int((diff > 0).sum())} of {diff.size} elements differ", flush=True)
    check(bool(np.all(diff <= bound)),
          "the bfloat16 kernel is off the oracle beyond one bfloat16 rounding")


def phase_four_chips() -> None:
    check(len(jax.devices()) == 4, f"--four-chips needs 4 chips, "
          f"found {len(jax.devices())}")
    out = run(QWEN3_FULL + ["--seq-len", "512"])
    check_qwen3(out, n_nodes=4)
    report_run("phase c", out)
    schedule, params, mesh = out["schedule"], out["params"], out["mesh"]
    print(f"phase c: losses {out['losses']}; schedule of {schedule.n_atoms} "
          f"atoms, {schedule.n_communication_atoms} communicating", flush=True)

    # each node's slice of every leaf on a chip of its own
    for leaf in jax.tree_util.tree_leaves(params):
        owner = {s.index[0].start or 0: s.device.id for s in leaf.addressable_shards
                 if s.replica_id == 0}
        check(sorted(owner) == [0, 1, 2, 3] and len(set(owner.values())) == 4,
              f"nodes of a {leaf.shape} leaf are not on 4 distinct chips: {owner}")

    # Every node starts from one init, so the final rows are nearly equal
    # and W @ theta ~ theta for any W: mix the final parameters plus seeded
    # noise of each leaf's own scale, which differs by node.
    def perturb(tree):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        keys = jax.random.split(jax.random.PRNGKey(NOISE_SEED), len(leaves))
        return treedef.unflatten([
            (x.astype(jnp.float32) + (jnp.abs(x.astype(jnp.float32)).mean() + 1e-2)
             * jax.random.normal(k, x.shape)).astype(x.dtype)
            for x, k in zip(leaves, keys)])

    n = schedule.n_nodes
    # the planted control: each atom's permutation shifted by one node
    shifted = BirkhoffSchedule(coeffs=schedule.coeffs, perms=tuple(
        tuple(p[(i + 1) % n] for i in range(n)) for p in schedule.perms))
    W = np.asarray(schedule.to_matrix(), np.float32)
    check(not np.allclose(W, shifted.to_matrix()),
          "the shifted schedule has the same W: it controls nothing")
    specs = out["setup"].param_specs
    with jax.set_mesh(mesh):
        theta = jax.jit(perturb, out_shardings=jax.tree_util.tree_map(
            lambda x: x.sharding, params))(params)
        mix = jax.jit(gossip_fn(mesh, schedule, "data", specs))
        tic = time.perf_counter()
        mixed = jax.block_until_ready(mix(theta))
        mix_s = time.perf_counter() - tic
        wrong = jax.jit(gossip_fn(mesh, shifted, "data", specs))(theta)

    worst, outside, total = 0.0, {"unmixed": 0, "shifted": 0}, 0
    for got, bad, x in zip(*(jax.tree_util.tree_leaves(t)
                             for t in (mixed, wrong, theta))):
        t = np.asarray(x.astype(jnp.float32)).reshape(n, -1)
        want = W @ t
        # at most 2L bf16 roundings (one per product, one per sum) of
        # unit roundoff 2^-8, each relative to |W| |theta|
        bound = 2 * schedule.n_atoms * 2.0**-8 * (np.abs(W) @ np.abs(t))
        g = np.asarray(got.astype(jnp.float32)).reshape(n, -1)
        check(bool(np.all(np.abs(g - want) <= bound)),
              f"ppermute mixing of a {x.shape} leaf is off the dense "
              f"float32 W @ theta")
        worst = max(worst, float(np.abs(g - want).max() / np.abs(want).max()))
        total += t.size
        for name, c in (("unmixed", t),
                        ("shifted", np.asarray(bad.astype(jnp.float32)).reshape(n, -1))):
            miss = int((np.abs(c - want) > bound).sum())
            check(miss > 0, f"the {name} control of a {x.shape} leaf passes "
                  f"the bound: the check cannot tell it from the mixing")
            outside[name] += miss
    print(f"phase c: nodes on 4 distinct chips; ppermute mixing of the "
          f"perturbed final parameters vs dense float32 W @ theta: max |diff| "
          f"/ max |W theta| {worst:.3e} over all leaves, every element inside "
          f"the bound; controls outside the bound on every leaf: unmixed "
          f"{outside['unmixed']} and shifted permutations {outside['shifted']} "
          f"of {total} elements; mixing call incl. compile {mix_s:.2f}s; "
          f"peak_bytes_in_use {peak_bytes()}", flush=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-node, four-chip phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    print(f"platform {dev.platform}  device_kind {dev.device_kind}  "
          f"count {len(jax.devices())}; compile cache {enable_compile_cache()}",
          flush=True)
    if args.four_chips:
        phase_four_chips()
    else:
        phase_trainer()
        phase_gossip_kernel()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
