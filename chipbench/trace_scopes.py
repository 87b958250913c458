"""The step's phase and part shares of one cell, from a traced window.

    python chipbench/trace_scopes.py --workload <cell> --seed <n> [--seconds 20]
        [--layers L] [--rows R] [--trace-seconds T] [--out DIR]

Sets the cell up as a run of ``run.py`` does (``harness.Bench``), times
``--seconds`` of steps untraced, then traces one step and
``trace_seconds`` of steps as a ``--trace 1`` run does. It joins the
trace to the compiled step's HLO text by instruction name
(``scopes.op_scopes``) and prints one JSON line: the shares of
``scopes.shares`` with the idle share; the per-block breakdown, the
share of every scope of ``scopes.SCOPES`` (``scope_shares``); how many
fusions, and how much of the window, ``op_scopes`` names by their root
or by the last op_name of their computation where XLA's own op_name
names a different phase (``renamed``); the step times traced and
untraced; what ``as_text()`` and the reduction cost; and the busiest
operations that no phase holds. The compile cache is keyed by the
program's metadata (``scopes.enable_cache``). ``--layers`` and
``--rows`` cut the cell; ``--out`` keeps the ``.xplane.pb`` and the
gzipped HLO text there. Exits 2 where JAX finds no TPU.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from chipbench import harness, reduce, scopes, spec  # noqa: E402


def unscoped_ops(own, op_scopes, k=8):
    """The ``k`` operations no phase holds with the most own time in the
    window: [name, op_name, share of the window in %]."""
    top = sorted(((name, v) for name, v in own.items()
                  if scopes.phase(op_scopes.get(name, "")) is None),
                 key=lambda kv: -kv[1])[:k]
    return [[name, op_scopes.get(name, ""), 100.0 * v] for name, v in top]


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="python chipbench/trace_scopes.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--layers", type=int)
    ap.add_argument("--rows", type=int)
    ap.add_argument("--trace-seconds", type=float)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    cfg, mix = dict(cell.config), dict(cell.traffic)
    if args.layers is not None:
        cfg["num_hidden_layers"] = args.layers
    if args.rows is not None:
        mix["rows_per_node"] = args.rows
    if args.trace_seconds is not None:
        mix["trace_seconds"] = args.trace_seconds
    cell = dataclasses.replace(cell, config=cfg, traffic=mix)
    scopes.enable_cache()
    try:
        devices = harness.check_devices(cell.chips)[: cell.chips]
    except harness.NoChip as e:
        print(f"trace_scopes: {e}", file=sys.stderr)
        return 2

    bench = harness.Bench(cell, devices)
    params, batches, _ = bench.start(args.seed)
    setup_s = time.perf_counter() - T_START
    params, plain, plain_window, _, _ = bench.steps(
        params, batches, args.seconds, harness.WARM_STEPS)

    out_dir = tempfile.mkdtemp(prefix="chipbench-scopes-")
    try:
        jax.profiler.start_trace(out_dir)
        try:
            params, _, _ = bench.step(params, None, batches[0])
            jax.block_until_ready(params)
            params, traced, _, _, _ = bench.steps(
                params, batches, mix["trace_seconds"], harness.WARM_STEPS)
        finally:
            jax.profiler.stop_trace()
        pb = next(Path(out_dir).rglob("*.xplane.pb"))
        t0 = time.perf_counter()
        tr = reduce.load(pb)
        t1 = time.perf_counter()
        text = bench.step.as_text()
        t2 = time.perf_counter()
        op_scopes = scopes.op_scopes(text)
        t3 = time.perf_counter()
        shares = scopes.shares(tr, op_scopes)
        t4 = time.perf_counter()
        moved = scopes.renamed(text)
        if args.out:
            Path(args.out).mkdir(parents=True, exist_ok=True)
            shutil.copy(pb, Path(args.out) / "trace.xplane.pb")
            with gzip.open(Path(args.out) / "step.hlo.txt.gz", "wt") as f:
                f.write(text)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)

    if not tr.devices:
        print("trace_scopes: no device operations in the trace", file=sys.stderr)
        return 1
    idle = 100.0 * reduce.idle_share(tr)
    phases = sum(shares.get(k, 0.0) for k in ("forward_share", "backward_share",
                                              "remat_share", "update_share"))
    own = scopes.own_time(tr)
    print(json.dumps({
        "workload": args.workload, "layers": cfg["num_hidden_layers"],
        "rows": mix["rows_per_node"], "seed": args.seed,
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "setup_s": setup_s,
        "step_s_untraced": statistics.median(plain), "steps_untraced": len(plain),
        "window_s_untraced": plain_window,
        "step_s_traced": statistics.median(traced), "steps_traced": len(traced),
        "window_s_traced": tr.window_s,
        "shares": shares, "device_idle_share": idle,
        "phases_and_idle": phases + idle,
        "scope_shares": scopes.scope_shares(tr, op_scopes),
        "renamed": {rule: [sum(1 for r in moved.values() if r[0] == rule),
                           100.0 * sum(own.get(n, 0.0) for n, r in moved.items()
                                       if r[0] == rule)]
                    for rule in ("root", "last")},
        "unscoped_ops": unscoped_ops(own, op_scopes),
        "load_s": t1 - t0, "as_text_s": t2 - t1, "op_scopes_s": t3 - t2,
        "shares_s": t4 - t3, "hlo_bytes": len(text), "instructions": len(op_scopes),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
