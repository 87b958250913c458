import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, with NO real device allocation (ShapeDtypeStruct
inputs only).

The two lines above MUST stay the first statements of this module: jax locks
the device count at first backend initialization, and the dry-run needs 512
placeholder host devices to build the 2x16x16 production mesh. Tests and
benchmarks import other modules and keep seeing 1 device.

Per combo this produces:
  * compiled.memory_analysis()  -- per-device argument/temp/output bytes
  * compiled.cost_analysis()    -- HLO FLOPs / bytes accessed (NOTE: XLA
    counts while-loop bodies ONCE; repro.launch.roofline corrects for the
    layer-scan trip counts)
  * collective statistics parsed from the post-SPMD HLO text (per type,
    loop-aware)
written to experiments/dryrun/<arch>__<shape>__<mesh>.json.

Shape kinds: train_4k lowers train_step; prefill_32k lowers the prefill
path; decode_32k / long_500k lower serve_step (ONE token against a
seq_len-sized cache; long_500k uses the sub-quadratic window/recurrent
state). Whisper skips decode shapes (enc-dec, max target length 448 --
DESIGN.md).
"""

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.serve.engine import make_serve_setup, prefill as engine_prefill
from repro.train.lm_trainer import make_train_setup

SKIPS: dict[tuple[str, str], str] = {
    ("whisper-small", "decode_32k"): "enc-dec ASR: decoder max target len 448",
    ("whisper-small", "long_500k"): "enc-dec ASR: decoder max target len 448",
}

# archs that need sliding-window *variants* for long_500k (pure full-attn
# families) -- permitted by the brief, recorded in DESIGN.md.
_COLLECTIVE_RE = re.compile(
    r"(\bf\d+|bf16|u\d+|s\d+|pred)\[([0-9,]*)\][^=]*\b"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)\b"
)

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "f64": 8, "u32": 4, "s32": 4,
                "u8": 1, "s8": 1, "pred": 1, "u64": 8, "s64": 8, "f8": 1}


def train_mode_for(arch: str, multi_pod: bool) -> str:
    if multi_pod:
        return "dsgd_pod"
    if arch == "deepseek-v2-236b":
        return "fsdp"  # 16 replicas do not fit a pod (DESIGN.md)
    return "dsgd"


def parse_collectives(hlo_text: str, scan_trip: int) -> dict:
    """Sum collective result bytes from post-SPMD HLO, weighting ops that
    live inside while-loop bodies by ``scan_trip`` (the layer-scan length --
    XLA prints loop bodies once)."""
    body_names = set(re.findall(r"body=%?([\w.\-]+)", hlo_text))
    totals = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
              "all-to-all": 0, "collective-permute": 0}
    current_mult = 1
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # computation headers: "%name (args...) -> ... {" or "ENTRY %name ...{".
        # args may contain nested parens (tuple params), so match only the
        # leading name token.
        if stripped.endswith("{") and (stripped.startswith("%") or stripped.startswith("ENTRY")):
            tok = stripped.split()[1] if stripped.startswith("ENTRY") else stripped.split()[0]
            name = tok.lstrip("%").split("(")[0]
            current_mult = scan_trip if name in body_names else 1
        m = _COLLECTIVE_RE.search(line)
        if m:
            dtype, dims, kind = m.groups()
            nelems = 1
            if dims:
                for d in dims.split(","):
                    if d:
                        nelems *= int(d)
            totals[kind] += nelems * _DTYPE_BYTES.get(dtype, 4) * current_mult
    totals["total_bytes"] = sum(totals.values())
    return totals


def scan_trip_count(cfg) -> int:
    return max(cfg.num_layers // len(cfg.layer_pattern), 1)


def _param_shardings(specs, mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )


# §Perf: microbatching policy -- archs whose activation footprint exceeds
# HBM at the full per-step batch accumulate gradients over microbatches.
GRAD_ACCUM = {"deepseek-v2-236b": 8, "qwen3-moe-30b-a3b": 2}


def build_train_lowering(arch: str, shape: dict, mesh, multi_pod: bool):
    cfg = get_config(arch)
    mode = train_mode_for(arch, multi_pod)
    setup = make_train_setup(cfg, mesh, mode=mode, schedule=None, lr=1e-3,
                             grad_accum=GRAD_ACCUM.get(arch, 1))
    gb, S = shape["global_batch"], shape["seq_len"]
    if mode == "dsgd":
        n = setup.n_nodes
        lead = (n, gb // n)
    elif mode == "dsgd_pod":
        n = setup.n_nodes
        lead = (n, gb // n)
    else:
        lead = (gb,)

    def batch_abs():
        ex = registry.make_inputs(cfg, batch_size=1, seq_len=S, abstract=True)
        out = {}
        for k, v in ex.items():
            out[k] = jax.ShapeDtypeStruct(lead + v.shape[1:], v.dtype)
        return out

    batch = batch_abs()
    bspec = {}
    for k, v in batch.items():
        spec = setup.batch_spec(v.ndim)
        bspec[k] = NamedSharding(mesh, spec)
    params_abs = setup.abstract_params()
    shardings = _param_shardings(setup.param_specs, mesh)
    jitted = jax.jit(
        setup.train_step,
        in_shardings=(shardings, None, bspec),
        donate_argnums=(0,),  # params updated in place
    )
    lowered = jitted.lower(params_abs, None, batch)
    return cfg, lowered, {"mode": mode}


def build_decode_lowering(arch: str, shape: dict, mesh, multi_pod: bool, long: bool):
    cfg = get_config(arch)
    B, S = shape["global_batch"], shape["seq_len"]
    setup = make_serve_setup(cfg, mesh, batch=B, seq_len=S, long_context=long)
    params_abs = jax.eval_shape(
        lambda r: registry.init_model(r, cfg), jax.random.PRNGKey(0)
    )
    pshard = _param_shardings(setup.param_specs, mesh)
    cshard = _param_shardings(setup.cache_specs, mesh)
    dp = ("pod", "data") if multi_pod else ("data",)
    dp_axis = tuple(dp) if len(dp) > 1 else dp[0]
    tok_spec = NamedSharding(mesh, P(dp_axis if B > 1 else None, None))
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    position = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    jitted = jax.jit(
        setup.serve_step,
        in_shardings=(pshard, tok_spec, tok_spec, cshard),
        donate_argnums=(3,),  # in-place cache update: no double-buffer temp
    )
    lowered = jitted.lower(params_abs, token, position, setup.abstract_cache)
    return cfg, lowered, {"mode": "serve_decode" + ("_long" if long else "")}


def build_prefill_lowering(arch: str, shape: dict, mesh, multi_pod: bool):
    cfg = get_config(arch)
    B, S = shape["global_batch"], shape["seq_len"]
    from repro.train.sharding import make_param_specs

    params_abs = jax.eval_shape(
        lambda r: registry.init_model(r, cfg), jax.random.PRNGKey(0)
    )
    pspecs = make_param_specs(params_abs, mesh, node_axis=None, fsdp_axis=None)
    pshard = _param_shardings(pspecs, mesh)
    dp = ("pod", "data") if multi_pod else ("data",)
    dp_axis = tuple(dp) if len(dp) > 1 else dp[0]

    inputs = registry.make_inputs(cfg, batch_size=B, seq_len=S, abstract=True)
    in_shardings = {}
    for k, v in inputs.items():
        in_shardings[k] = NamedSharding(mesh, P(dp_axis, *([None] * (v.ndim - 1))))

    def prefill_step(params, batch):
        if cfg.arch_type == "audio":
            return engine_prefill(
                params, cfg, batch["tokens"], max_len=batch["tokens"].shape[1] + 8,
                frames=batch["frames"],
            )
        img = batch.get("image_embeds")
        return engine_prefill(
            params, cfg, batch["tokens"],
            max_len=S + 8, image_embeds=img,
        )

    inputs.pop("labels", None)
    in_shardings.pop("labels", None)
    jitted = jax.jit(prefill_step, in_shardings=(pshard, in_shardings))
    lowered = jitted.lower(params_abs, inputs)
    return cfg, lowered, {"mode": "serve_prefill"}


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str) -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    key = f"{arch}__{shape_name}__{mesh_name}"
    if (arch, shape_name) in SKIPS:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "skipped", "reason": SKIPS[(arch, shape_name)]}
        _write(out_dir, key, rec)
        print(f"SKIP {key}: {rec['reason']}")
        return rec
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            if shape["kind"] == "train":
                cfg, lowered, meta = build_train_lowering(arch, shape, mesh, multi_pod)
            elif shape["kind"] == "prefill":
                cfg, lowered, meta = build_prefill_lowering(arch, shape, mesh, multi_pod)
            else:
                long = shape["kind"] == "decode_long"
                cfg, lowered, meta = build_decode_lowering(arch, shape, mesh, multi_pod, long)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis() or {}
            hlo = compiled.as_text()
            trip = scan_trip_count(cfg)
            coll = parse_collectives(hlo, trip)
        rec = {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "status": "ok", **meta,
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
            },
            "cost": {
                "flops_per_device_hlo": ca.get("flops", 0.0),
                "bytes_accessed_hlo": ca.get("bytes accessed", 0.0),
            },
            "collectives": coll,
            "scan_trip": trip,
            "hlo_bytes": len(hlo),
        }
        print(
            f"OK   {key}: compile {t_compile:.0f}s | "
            f"temp {ma.temp_size_in_bytes/2**30:.2f} GiB/dev | "
            f"coll {coll['total_bytes']/2**20:.1f} MiB/dev"
        )
    except Exception as e:  # noqa: BLE001 - record failures, don't crash the sweep
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
        print(f"FAIL {key}: {rec['error'][:200]}")
    _write(out_dir, key, rec)
    return rec


def _write(out_dir: str, key: str, rec: dict) -> None:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, key + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


def _run_subprocess(arch: str, shape: str, multi_pod: bool, out_dir: str) -> dict:
    """Run one combo in an isolated process (XLA CHECK failures abort the
    whole process; isolation keeps the sweep alive) and read back its JSON."""
    import subprocess
    import sys

    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--arch", arch, "--shape", shape, "--out", out_dir]
    if multi_pod:
        cmd.append("--multi-pod")
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=3600)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    key = f"{arch}__{shape}__{mesh_name}"
    path = os.path.join(out_dir, key + ".json")
    if os.path.exists(path):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("status") != "error" or "traceback" in rec:
            return rec
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "status": "error",
           "error": f"process died (rc={proc.returncode})",
           "stderr_tail": proc.stderr[-1500:]}
    _write(out_dir, key, rec)
    print(f"FAIL {key}: process died rc={proc.returncode}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--subprocess", action="store_true",
                    help="isolate each combo in its own process")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    n_ok = n_fail = n_skip = 0
    for multi_pod in meshes:
        for arch in archs:
            for shape in shapes:
                if args.subprocess:
                    rec = _run_subprocess(arch, shape, multi_pod, args.out)
                else:
                    rec = run_one(arch, shape, multi_pod, args.out)
                n_ok += rec["status"] == "ok"
                n_fail += rec["status"] == "error"
                n_skip += rec["status"] == "skipped"
    print(f"\ndry-run summary: {n_ok} ok, {n_fail} failed, {n_skip} skipped")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
