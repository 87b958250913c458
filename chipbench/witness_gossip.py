"""Why ``qwen3-0.6b.4node.stlfw2`` is not a cell: the gossip's rounding.

    python chipbench/witness_gossip.py --seeds 1,2,3 [--out <file.jsonl>]

For each seed, set-up's three steps of the four-node step as a run
reads them, against the reference, on two paths of the program: the
learned STL-FW schedule as bfloat16 ``mix_ppermute`` (the cell's path);
the complete graph, whose ``pmean`` mixes in float32 and rounds once;
and the cell's path with float32 weights. One JSON line per seed and
path.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import compare, harness, spec  # noqa: E402

BASE, MIX = "qwen3-0.6b.1node.seq4k", "4node.stlfw2"


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--cpu", action="store_true", help="a 2-layer cut on CPU devices")
    args = ap.parse_args(argv)
    # the one-node cell's configuration under the four-node mix; the gaps
    # are printed, not judged
    traffic = spec.load_traffic(MIX)
    cell = dataclasses.replace(spec.load_cell(BASE), name="qwen3-0.6b.4node.stlfw2",
                               chips=traffic["nodes"], traffic_name=MIX,
                               traffic=traffic)
    if args.cpu:
        cell = dataclasses.replace(
            cell, config=dict(cell.config, hidden_size=256, num_hidden_layers=2,
                              num_attention_heads=4, num_key_value_heads=2,
                              head_dim=64, intermediate_size=512, vocab_size=2048),
            traffic=dict(cell.traffic, seq_len=1024))
    else:
        from repro.launch.cache import enable_compile_cache

        enable_compile_cache()
    devices = (jax.devices() if args.cpu else harness.check_devices(cell.chips))[: cell.chips]
    n = cell.traffic["nodes"]
    complete = dataclasses.replace(cell, traffic=dict(
        cell.traffic, topology="complete", mixing_matrix=np.full((n, n), 1 / n).tolist()))
    out = open(args.out, "a") if args.out else None
    f32_weights = dataclasses.replace(cell, config=dict(cell.config, torch_dtype="float32"))
    for path, c in (("stl-fw mix_ppermute, bf16 weights", cell),
                    ("complete-graph pmean in f32, bf16 weights", complete),
                    ("stl-fw mix_ppermute, f32 weights", f32_weights)):
        bench = harness.Bench(c, devices)
        ref = harness.Reference(c, devices)
        for seed in [int(s) for s in args.seeds.split(",")]:
            params, batches, read = bench.start(seed)
            del params
            batches = [jax.device_get(b) for b in batches]
            gc.collect()
            line = {"path": path, "seed": seed,
                    **compare.gaps(read, ref.readings(seed, batches)),
                    "t": time.perf_counter() - T_START}
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
        del bench, ref
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
