"""One run of one cell: set-up, the measured window or the traced
stretch, then the comparison with the reference.

Set-up builds the program's step once, makes the weights and a pool of
input batches on the device from the seed, and drives the compiled step
through its first three steps on pool batches 0-2, reading what the
comparison needs from its state. The window then calls the same
compiled step back to back on the pool, from where set-up left it. Each
step is timed on the host clock to the loss's arrival; nothing compiles
inside (a compile there fails the run). After the window the device's
peak memory is read, the program's state is freed, and the reference
takes the same three steps from the same weights and batches. What
belongs to the model (its weights, their paths in the program's tree,
its reference layer and FLOPs) comes from the cell's family.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from . import compare, flops, program, reduce, reference, scopes, spec, weights
from . import traffic as traffic_mod

WARM_STEPS = 3  # set-up's steps: the comparison reads the first three
# every cell: no compile inside the window, and the program learned the
# mixing matrix the traffic file states (to float64 rounding of the solve)
FIXED_LIMITS = {"window_compiles": 0, "topology": 1e-9}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def count_compiles():
    """Collects JAX's backend compiles (a persistent-cache load counts too)
    while the block runs."""
    seen = []

    def on_event(event, _duration, **_):
        if event == COMPILE_EVENT:
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


@dataclasses.dataclass
class RunRecord:
    """What a run measured; the metric readers read it."""

    chips: int
    setup_s: float
    step_s: list[float]
    window_s: float
    tokens_per_step: int
    flops_per_step: float
    peak_flops: float | None
    config: dict  # the cell's configuration file, as run
    traffic: dict  # the cell's traffic mix
    trace: reduce.Trace | None = None
    # {instruction: op_name} of the compiled step (``scopes.op_scopes``);
    # traced runs only
    op_scopes: dict[str, str] | None = None


class Bench:
    """The program of one cell, built and compiled once in a process."""

    def __init__(self, cell: spec.Cell, devices):
        self.cfg, self.traffic = cell.config, cell.traffic
        fam = cell.family
        self.prog = program.build(fam.model_config(self.cfg), self.traffic)
        self.lr = self.traffic["lr"]
        setup, cfg, n = self.prog.setup, self.cfg, self.traffic["nodes"]
        paths = fam.paths(cfg)

        def init(key):
            return program.to_program(
                program.broadcast_nodes(weights.init(key, cfg, fam), n), setup, paths)

        self.init = jax.jit(init, out_shardings=self.prog.param_shardings)
        pool = traffic_mod.make_pool_fn(self.traffic, cfg["vocab_size"])
        self.pool = jax.jit(lambda k: [program.place_batch(r) for r in pool(k)],
                            out_shardings=self.prog.batch_sharding)
        self.diff_norms = jax.jit(lambda a, b, scale: reference.diff_norms(
            program.from_program(a, paths), program.from_program(b, paths), scale))
        self.step = None

    def start(self, seed: int):
        """Set-up from ``seed``: weights, pool, compile, three steps.

        Returns (params after the steps, pool, program readings).
        """
        params = self.init(weights.seed_key(seed, weights.STREAM_WEIGHTS))
        batches = self.pool(weights.seed_key(seed, weights.STREAM_TRAFFIC))
        if self.step is None:
            self.step = jax.jit(self.prog.setup.train_step).lower(
                params, None, batches[0]).compile()
        losses, grad = [], None
        for t in range(WARM_STEPS):
            nxt, _, loss = self.step(params, None, batches[t % len(batches)])
            losses.append(float(loss))
            if t == 0:
                grad = self.diff_norms(params, nxt, self.lr)
            params = nxt
        theta0 = self.init(weights.seed_key(seed, weights.STREAM_WEIGHTS))
        change = self.diff_norms(params, theta0, 1.0)
        del theta0
        readings = {"losses": losses, "grad": jax.device_get(grad),
                    "change": jax.device_get(change)}
        return params, batches, readings

    def steps(self, params, batches, seconds: float, first: int):
        """Back-to-back steps until ``seconds`` have passed.

        Returns (params, step times, window seconds, failed steps, compiles).
        """
        times, failed, i = [], 0, first
        with count_compiles() as compiles:
            begin = time.perf_counter()
            while True:
                t0 = time.perf_counter()
                with TraceAnnotation("bench.input"):
                    batch = batches[i % len(batches)]
                with TraceAnnotation("bench.dispatch"):
                    params, _, loss = self.step(params, None, batch)
                with TraceAnnotation("bench.wait"):
                    value = float(loss)
                t1 = time.perf_counter()
                times.append(t1 - t0)
                failed += not math.isfinite(value)
                i += 1
                if t1 - begin >= seconds:
                    break
        return params, times, t1 - begin, failed, len(compiles)


class Reference:
    """The reference's three steps from a seed's weights and batches.

    ``mm``, ``half`` and ``W`` put the control (``reference.fp8_mm``) or
    a fault (half the batch; ``W = I``: no exchange) in its place.
    """

    def __init__(self, cell: spec.Cell, devices, *, mm=reference.f32_mm,
                 half: bool = False, W: np.ndarray | None = None):
        cfg, traffic, fam = cell.config, cell.traffic, cell.family
        n = traffic["nodes"]
        self.lr = traffic["lr"]
        mesh = jax.make_mesh((n,), ("node",), devices=list(devices)[:n],
                             axis_types=(jax.sharding.AxisType.Auto,))
        self.by_node = NamedSharding(mesh, P("node"))
        self.W = jnp.asarray(traffic["mixing_matrix"] if W is None else W,
                             jnp.float32)
        self.init = jax.jit(
            lambda k: program.broadcast_nodes(weights.init(k, cfg, fam), n),
            out_shardings=self.by_node)
        self.step = jax.jit(reference.make_step(cfg, self.lr, fam, mm=mm, half=half),
                            out_shardings=(self.by_node, None, None))
        self.norms = jax.jit(reference.diff_norms)

    def readings(self, seed: int, batches) -> dict:
        key = weights.seed_key(seed, weights.STREAM_WEIGHTS)
        theta, losses = self.init(key), []
        for t in range(WARM_STEPS):
            batch = jax.device_put(batches[t % len(batches)], self.by_node)
            nxt, loss, gnorm = self.step(theta, batch, self.W)
            losses.append(float(loss))
            if t == 0:
                grad_exact = jax.device_get(gnorm)
                grad = jax.device_get(self.norms(theta, nxt, self.lr))
            theta = nxt
        change = jax.device_get(self.norms(theta, self.init(key), 1.0))
        return {"losses": losses, "grad": grad, "change": change,
                "grad_exact": grad_exact}


def memory_peak(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def check_devices(chips: int):
    devices = jax.devices()
    d = devices[0]
    print(f"platform {d.platform}  device_kind {d.device_kind}  "
          f"count {len(devices)}", file=sys.stderr, flush=True)
    if d.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {d.platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return devices


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    """One run of ``cell``; returns the result line's object."""
    devices = check_devices(cell.chips)[: cell.chips]
    kind = devices[0].device_kind
    peak = spec.load_peaks(kind)["bf16_flops_per_s"]

    bench = Bench(cell, devices)
    params, batches, prog_read = bench.start(seed)
    setup_s = time.perf_counter() - t_start

    tr = op_scopes = None
    if trace:
        out_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        try:
            jax.profiler.start_trace(out_dir)
            try:
                # one step outside the window takes the profiler's start-up stall
                params, _, _ = bench.step(params, None, batches[0])
                jax.block_until_ready(params)
                params, times, window_s, failed, compiles = bench.steps(
                    params, batches, cell.traffic["trace_seconds"], WARM_STEPS)
            finally:
                jax.profiler.stop_trace()
            tr = reduce.load(next(Path(out_dir).rglob("*.xplane.pb")))
            op_scopes = scopes.op_scopes(bench.step.as_text())
        finally:
            shutil.rmtree(out_dir, ignore_errors=True)
    else:
        params, times, window_s, failed, compiles = bench.steps(
            params, batches, seconds, WARM_STEPS)
    peak_bytes = memory_peak(devices)

    values = {"window_compiles": float(compiles),
              "topology": float(np.max(np.abs(
                  bench.prog.W - np.asarray(cell.traffic["mixing_matrix"]))))}
    del params, bench
    gc.collect()
    host_batches = [jax.device_get(b) for b in batches]
    del batches
    ref_read = Reference(cell, devices).readings(seed, host_batches)
    values.update(compare.gaps(prog_read, ref_read))
    correct, checks = compare.judge(values, {**FIXED_LIMITS, **cell.limits})

    tp = cell.traffic
    rec = RunRecord(
        chips=cell.chips, setup_s=setup_s, step_s=times,
        window_s=window_s,
        tokens_per_step=tp["nodes"] * tp["rows_per_node"] * tp["seq_len"],
        flops_per_step=flops.step_flops(cell.config, tp, cell.family),
        peak_flops=peak, config=cell.config, traffic=tp, trace=tr,
        op_scopes=op_scopes)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.read(rec)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": len(times), "failed": failed,
              "metrics": metrics, "device": device}
    if tr is not None and tr.devices:
        device.update(busy_s=reduce.busy_s(tr), window_s=tr.window_s)
        result["breakdown"] = {"device_ops": reduce.top_ops(tr),
                               "idle_gaps": reduce.idle_gaps(tr)}
    result["readings"] = values
    result["checks"] = checks
    return result


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="python chipbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_start: float, root: Path = spec.ROOT) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload, root)
    from repro.launch.cache import enable_compile_cache

    # a traced run reads the step's op_names, which only a cache entry
    # keyed by the metadata too keeps; an untraced run keys by the
    # instructions alone, as the program does
    if args.trace:
        scopes.enable_cache()
    else:
        enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace), t_start)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, v in result.pop("readings").items():
        print(f"reading {name} {v!r}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
