"""Why qwen3's tied embedding reads under the reference on ``grad``.

    python chipbench/witness_embed.py --workload <cell> --seeds 1,2,3 [--out <file.jsonl>]

For each seed, at the cell's size, the embedding leaf's gradient norm
four ways beside the float32 reference's (``grad_exact``) and the
reference's worked-out one (``grad``): the program's own step, worked
out from its state after one step; the program's bfloat16 gradient
(``registry.loss_fn`` under ``jax.grad``, as ``train_step`` takes it);
the same gradient applied to the weights with one rounding,
bf16(theta - lr * g) computed in float32; and the gradient of the
program with its input lookup gathered from a float32 copy of the table,
so that the lookup's transpose, a scatter-add of one row per token,
accumulates in float32. One JSON line per seed; each gap is signed,
(norm - reference) / reference.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import harness, spec, weights  # noqa: E402


@contextlib.contextmanager
def f32_lookup():
    """The program's input lookup gathers from a float32 copy of the table."""
    from repro.models import layers, transformer

    plain = transformer.embed

    def embed(params, tokens, cfg):
        table = params["table"]
        x = layers.embed({"table": table.astype(jnp.float32)}, tokens, cfg)
        return x.astype(table.dtype)

    transformer.embed = embed
    try:
        yield
    finally:
        transformer.embed = plain


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from repro.launch.cache import enable_compile_cache
    from repro.models import registry

    enable_compile_cache()
    cell = spec.load_cell(args.workload)
    devices = harness.check_devices(cell.chips)[: cell.chips]
    mcfg = cell.family.model_config(cell.config)
    lr = cell.traffic["lr"]
    bench = harness.Bench(cell, devices)
    ref = harness.Reference(cell, devices)

    def node0(tree):
        return jax.tree_util.tree_map(lambda x: x[0], tree)

    def embed_norms():
        """A new function each call, so that each path traces afresh:
        (params, batch) -> (||g||, ||theta - bf16(theta - lr g)|| / lr) of
        the table, node 0."""
        def norms(params, batch):
            p, b = node0(params), node0(batch)
            g = jax.grad(lambda q: registry.loss_fn(q, mcfg, b)[0])(p)["embed"]["table"]
            t = p["embed"]["table"].astype(jnp.float32)
            once = (t - lr * g.astype(jnp.float32)).astype(p["embed"]["table"].dtype)
            return (jnp.linalg.norm(g.astype(jnp.float32)),
                    jnp.linalg.norm(t - once.astype(jnp.float32)) / lr)
        return norms

    out = open(args.out, "a") if args.out else None
    grads = {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        params, batches, read = bench.start(seed)
        del params
        theta0 = bench.init(weights.seed_key(seed, weights.STREAM_WEIGHTS))
        for path, ctx in (("bf16 lookup", contextlib.nullcontext), ("f32 lookup", f32_lookup)):
            if path not in grads:
                with ctx():
                    grads[path] = jax.jit(embed_norms()).lower(theta0, batches[0]).compile()
        raw, once = (np.asarray(x) for x in grads["bf16 lookup"](theta0, batches[0]))
        raw32, once32 = (np.asarray(x) for x in grads["f32 lookup"](theta0, batches[0]))
        del theta0
        host = [jax.device_get(b) for b in batches]
        del batches
        gc.collect()
        r = ref.readings(seed, host)
        exact, worked = float(r["grad_exact"]["embed"][0]), float(r["grad"]["embed"][0])
        line = {
            "cell": cell.name, "seed": seed,
            "ref_grad": exact, "ref_worked_out": worked,
            "step_worked_out_gap": float(read["grad"]["embed"][0]) / worked - 1,
            "raw_grad_gap": float(raw) / exact - 1,
            "once_rounded_gap": float(once) / worked - 1,
            "raw_grad_f32_lookup_gap": float(raw32) / exact - 1,
            "once_rounded_f32_lookup_gap": float(once32) / worked - 1,
            "t": time.perf_counter() - T_START,
        }
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
