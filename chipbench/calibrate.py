"""Readings the limits of ``limits/<cell>.json`` are set from.

    python chipbench/calibrate.py --workload <cell> --seeds 1,2,.. \\
        --control-seeds 3,4,5 [--out <file.jsonl>]

In one process, for each seed: the program's gaps to the reference
(set-up's three steps, as a run reads them); for each control seed: the
control's gaps (the reference in float8 in the program's place) and each
fault's (half of the batch left out; with gossip, the exchange left
out), each planted in the reference in the program's place. A state
left unchanged reads 1 on ``grad`` and ``change`` by their definition
and needs no run. One JSON line per reading.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import compare, harness, reference, spec  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.load_cell(args.workload)
    devices = harness.check_devices(cell.chips)[: cell.chips]
    out = open(args.out, "a") if args.out else None

    def raw(read):
        return {k: (v if k == "losses" else
                    {n: np.asarray(a).tolist() for n, a in v.items()})
                for k, v in read.items()}

    def emit(kind, seed, read, ref):
        line = {"cell": cell.name, "kind": kind, "seed": seed,
                **compare.gaps(read, ref), "t": time.perf_counter() - T_START,
                "read": raw(read), "ref": raw(ref)}
        print(json.dumps({k: v for k, v in line.items() if k not in ("read", "ref")}), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()

    bench = harness.Bench(cell, devices)
    f32 = harness.Reference(cell, devices)
    batches_of = {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        params, batches, read = bench.start(seed)
        del params
        batches_of[seed] = [jax.device_get(b) for b in batches]
        del batches
        gc.collect()
        emit("program", seed, read, f32.readings(seed, batches_of[seed]))
    variants = {"control": harness.Reference(cell, devices, mm=reference.fp8_mm),
                "fault_half_batch": harness.Reference(cell, devices, half=True)}
    if cell.traffic["nodes"] > 1:
        variants["fault_no_exchange"] = harness.Reference(
            cell, devices, W=np.eye(cell.traffic["nodes"]))
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        if seed not in batches_of:
            _, batches, _ = bench.start(seed)
            batches_of[seed] = [jax.device_get(b) for b in batches]
            del batches
        ref = f32.readings(seed, batches_of[seed])
        for kind, runner in variants.items():
            emit(kind, seed, runner.readings(seed, batches_of[seed]), ref)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
