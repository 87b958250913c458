"""The step program's named scopes (``scopes.py``), and their shares of a
traced window.

On hand-made HLO text and intervals; on the step of a 2-layer qwen3 cut
compiled here on the CPU; on each cell's step compiled at the real size,
and a 2-layer cut of it, for a described TPU v5e (``v5e:2x2``; nothing
runs); on a trace recorded on the chip; and on MLA's attend and the
compile cache keyed by the scopes.

``testdata/tpu_scoped_2x1.xplane.pb`` was recorded on one TPU v5 lite
by ``trace_scopes.py --workload qwen3-0.6b.1node.seq4k --seed 2147483659
--layers 2 --rows 1 --seconds 5 --trace-seconds 0.4``: the scoped step
at 2 layers and 1 x 4096 tokens, compiled afresh, five traced steps, cut
down to the benchmark's host spans of the second and third and the
device operations that overlap them, without event stats.
``testdata/tpu_scoped_2x1.hlo.txt.gz`` is that step's ``as_text()``.

The described chip is held by a fixture of this file, as in
``test_chipbench_compile.py``: under several test workers that takes a
second process on the TPU's library, which ``ALLOW_MULTIPLE_LIBTPU_LOAD``
permits; without it the fixture skips.
"""

import collections
import contextlib
import dataclasses
import gzip
import json
import math
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from chipbench import program, reduce, scopes, spec
from repro.models import attention
from repro.models.common import MLAConfig, ModelConfig

BENCH = Path(__file__).resolve().parent
DATA = BENCH / "testdata"
GRAD = "jit(train_step)/dsgd.grad"
BODY = GRAD + "/transpose(jvp())/while/body/closed_call/checkpoint"


@pytest.mark.parametrize("op_name, phase, part", [
    ("jit(train_step)/dsgd.update/sub", "update", None),
    ("jit(train_step)/shard_map/dsgd.gossip/ppermute", "gossip", None),
    ("jit(train_step)/shard_map/dsgd.probes/psum", "probes", None),
    (BODY + "/rematted_computation/attn/sdpa/mul", "remat", "sdpa"),
    (BODY + "/attn/sdpa/bqhgd,bkhd->bhgqk/transpose", "backward", "sdpa"),
    (GRAD + "/transpose(jvp(lm_head))/while/body/closed_call", "backward", "lm_head"),
    (GRAD + "/jvp(lm_head)/while/body/closed_call/convert_element_type",
     "forward", "lm_head"),
    (GRAD + "/jvp()/while/body/closed_call/attn/sdpa/bqhgd,bkhd->bhgqk/transpose",
     "forward", "sdpa"),
    (GRAD + "/jvp()/while/body/closed_call/mlp/mul", "forward", None),
    # loop-invariant tables JAX hoisted out of the differentiated scan
    ("jit(train_step)/attn/qkv/cos", None, None),
    ("params['embed']['table']", None, None),
    ("", None, None),
    # the first of ;-joined op_names counts
    ("jit(train_step)/dsgd.update/mul;" + GRAD + "/jvp(lm_head)/x", "update", None),
    (GRAD + "/jvp(lm_head)/x;jit(train_step)/dsgd.update/mul", "forward", "lm_head"),
])
def test_phase_and_part(op_name, phase, part):
    assert scopes.phase(op_name) == phase
    assert scopes.part(op_name) == part


MODULE = """\
HloModule jit_train_step, entry_computation_layout={(f32[8]{0})->f32[]}

%fused_computation (param_0: f32[8], param_1: f32[8]) -> bf16[1,8] {
  %param_0 = f32[8]{0} parameter(0)
  %param_1 = f32[8]{0} parameter(1)
  %mul.1 = f32[8]{0} multiply(%param_0, %param_1), metadata={op_name="jit(train_step)/dsgd.update/mul"}
  %sub.2 = f32[8]{0} subtract(%param_0, %mul.1), metadata={op_name="jit(train_step)/dsgd.update/sub" stack_frame_id=3}
  %convert.3 = bf16[8]{0} convert(%sub.2)
  ROOT %bitcast.4 = bf16[1,8]{1,0} bitcast(%convert.3), metadata={op_name="jit(train_step)/broadcast_in_dim"}
}

%fused_computation.1 (param_0.1: bf16[4,8]) -> bf16[2,8] {
  %param_0.1 = bf16[4,8]{1,0} parameter(0)
  %constant.5 = s32[] constant(0), metadata={op_name="jit(train_step)/dsgd.grad/transpose(jvp(lm_head))/while/body/closed_call"}
  ROOT %dynamic-slice.6 = bf16[2,8]{1,0} dynamic-slice(%param_0.1, %constant.5, %constant.5), dynamic_slice_sizes={2,8}
}

%region_0.7 (a.8: f32[], b.9: f32[]) -> f32[] {
  %a.8 = f32[] parameter(0)
  %b.9 = f32[] parameter(1)
  ROOT %add.10 = f32[] add(%a.8, %b.9), metadata={op_name="reduce_sum"}
}

%fused_computation.2 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  ROOT %mul.15 = f32[8]{0} multiply(%param_0.2, %param_0.2), metadata={op_name="jit(train_step)/dsgd.grad/transpose(jvp())/mul"}
}

ENTRY %main.11 (Arg_0.1: f32[8], Arg_1.2: f32[8], Arg_2.3: bf16[4,8]) -> (bf16[1,8], bf16[2,8], f32[], f32[8]) {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="params"}
  %Arg_1.2 = f32[8]{0} parameter(1)
  %Arg_2.3 = bf16[4,8]{1,0} parameter(2)
  %subtract_bitcast_fusion = bf16[1,8]{1,0} fusion(%Arg_0.1, %Arg_1.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(train_step)/broadcast_in_dim"}
  %dynamic-slice_fusion = bf16[2,8]{1,0} fusion(%Arg_2.3), kind=kLoop, calls=%fused_computation.1
  %constant.12 = f32[] constant(0)
  %reduce.13 = f32[] reduce(%Arg_0.1, %constant.12), dimensions={0}, to_apply=%region_0.7, metadata={op_name="jit(train_step)/dsgd.grad/jvp()/reduce_sum;jit(train_step)/dsgd.update/x"}
  %multiply_fusion = f32[8]{0} fusion(%Arg_1.2), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(train_step)/dsgd.grad/transpose(jvp())/checkpoint/rematted_computation/mul"}
  ROOT %tuple.14 = (bf16[1,8]{1,0}, bf16[2,8]{1,0}, f32[], f32[8]) tuple(%subtract_bitcast_fusion, %dynamic-slice_fusion, %reduce.13, %multiply_fusion)
}
"""


def test_op_scopes_reads_every_computation():
    got = scopes.op_scopes(MODULE)
    # the update's x[None] root is looked through, to the subtract
    assert got["subtract_bitcast_fusion"] == "jit(train_step)/dsgd.update/sub"
    # a root without op_name: the last op_name of the fused computation
    assert scopes.phase(got["dynamic-slice_fusion"]) == "backward"
    assert scopes.part(got["dynamic-slice_fusion"]) == "lm_head"
    # XLA's own op_name holds a phase: it stays, whatever the root says
    assert scopes.phase(got["multiply_fusion"]) == "remat"
    assert scopes.renamed(MODULE) == {
        "subtract_bitcast_fusion": ("root", "jit(train_step)/broadcast_in_dim",
                                    "jit(train_step)/dsgd.update/sub"),
        "dynamic-slice_fusion": ("last", "", GRAD + "/transpose(jvp(lm_head))/while"
                                 "/body/closed_call")}
    assert scopes.phase(got["reduce.13"]) == "forward"
    assert got["mul.1"] == "jit(train_step)/dsgd.update/mul"
    assert got["add.10"] == "reduce_sum" and got["Arg_0.1"] == "params"
    assert not {"tuple.14", "constant.12", "Arg_1.2", "convert.3"} & set(got)
    assert scopes.scoped(got)
    assert not scopes.scoped({"fusion.1": "jit(train_step)/jvp()/mul"})


def _trace(devices, spans):
    return reduce.Trace(devices=devices, spans=sorted(spans))


OPS = {"while.1": GRAD + "/jvp()/while", "fusion.2": GRAD + "/jvp()/attn/sdpa/mul",
       "fusion.3": BODY + "/rematted_computation/mlp/mul",
       "fusion.4": GRAD + "/transpose(jvp(lm_head))/dot_general",
       "fusion.5": "jit(train_step)/dsgd.update/sub", "copy.6": ""}


def test_scope_share_adds_up_to_the_window():
    # window [0, 1000): a forward loop [0, 600) holding fusion.2 and the
    # recomputation fusion.3 (own time 60), the backward, the update, an
    # unscoped copy, then 50 idle; fusion.7 lies before the window
    dev0 = [(-100, -10, "fusion.7"), (0, 600, "while.1"), (10, 300, "fusion.2"),
            (300, 550, "fusion.3"), (600, 850, "fusion.4"), (850, 900, "fusion.5"),
            (900, 950, "copy.6")]
    dev1 = [(0, 1000, "fusion.4")]
    spans = [(0, 400, "bench.dispatch"), (400, 1000, "bench.wait")]
    tr = _trace({"/device:TPU:0": dev0, "/device:TPU:1": dev1}, spans)
    got = scopes.shares(tr, OPS)
    assert got == pytest.approx({
        "forward_share": 100 * (290 + 60) / 2000, "backward_share": 100 * 1250 / 2000,
        "remat_share": 100 * 250 / 2000, "update_share": 100 * 50 / 2000,
        "attention_share": 100 * 290 / 2000, "lm_head_share": 100 * 1250 / 2000,
        "unscoped_share": 100 * 50 / 2000})
    phases = sum(got[k] for k in ("forward_share", "backward_share", "remat_share",
                                  "update_share", "unscoped_share"))
    assert phases + 100 * reduce.idle_share(tr) == pytest.approx(100)
    # per block: an operation counts for every scope on its path
    assert scopes.scope_shares(tr, OPS) == pytest.approx(dict.fromkeys(scopes.SCOPES, 0.0) | {
        "dsgd.grad": 100 * 1850 / 2000, "dsgd.update": 100 * 50 / 2000,
        "attn": 100 * 290 / 2000, "sdpa": 100 * 290 / 2000,
        "mlp": 100 * 250 / 2000, "lm_head": 100 * 1250 / 2000})


def test_shares_read_nothing_without_scopes():
    tr = _trace({"/device:TPU:0": [(0, 10, "fusion.2")]}, [(0, 10, "bench.wait")])
    assert scopes.shares(tr, {"fusion.2": "jit(train_step)/jvp()/mul"}) == {}
    assert scopes.shares(_trace({}, [(0, 10, "bench.wait")]), OPS) == {}
    assert scopes.scope_shares(tr, {"fusion.2": "jit(train_step)/jvp()/mul"}) == {}


def known_renames(hlo_text):
    """``scopes.renamed`` of a module, asserting that it moves a fusion off
    the phase of XLA's own op_name only in the two known cases: XLA gave
    the fusion no op_name, or the fusion is the update's or the gossip's
    ``x[None]``, whose bitcast root XLA names after the broadcast outside
    every scope."""
    moved = scopes.renamed(hlo_text)
    assert {name: r for name, r in moved.items() if not (
        r[1] == "" or r[0] == "root" and r[1].endswith("/broadcast_in_dim")
        and scopes.phase(r[2]) in ("update", "gossip"))} == {}
    return moved


def test_recorded_scoped_trace():
    """The six shares of two steps recorded on the chip, pinned; with the
    unscoped rest (copies XLA put in) and the idle share they fill the
    window, less what the async copies that overlap compute count twice.
    The per-block breakdown, and the fusions named by their root or by
    the last op_name of their computation, are pinned too."""
    tr = reduce.load(DATA / "tpu_scoped_2x1.xplane.pb")
    with gzip.open(DATA / "tpu_scoped_2x1.hlo.txt.gz", "rt") as f:
        text = f.read()
    op_scopes = scopes.op_scopes(text)
    got = scopes.shares(tr, op_scopes)
    assert got == pytest.approx({
        "forward_share": 21.15801427373684, "backward_share": 41.309830751048935,
        "remat_share": 31.79441840168648, "update_share": 1.72952149755487,
        "attention_share": 49.31960532744388, "lm_head_share": 37.521915171877126,
        "unscoped_share": 2.0539426497559643}, rel=1e-9)
    idle = 100 * reduce.idle_share(tr)
    phases = sum(got[k] for k in ("forward_share", "backward_share", "remat_share",
                                  "update_share"))
    assert 95 <= phases + idle <= 100 <= phases + got["unscoped_share"] + idle < 101
    assert scopes.scope_shares(tr, op_scopes) == pytest.approx({
        "dsgd.grad": 94.26226342647226, "dsgd.update": 1.72952149755487,
        "dsgd.gossip": 0.0, "dsgd.probes": 0.0, "embed": 0.649393475151149,
        "attn": 52.35890752844935, "qkv": 2.1577466539122234,
        "sdpa": 49.31960532744388, "out": 0.809542014697243,
        "mlp": 3.5854719652742153, "lm_head": 37.521915171877126}, rel=1e-9)
    moved = known_renames(text)
    own = scopes.own_time(tr)
    by_rule = {rule: (sum(1 for r in moved.values() if r[0] == rule),
                      100 * sum(own.get(n, 0.0) for n, r in moved.items()
                                if r[0] == rule))
               for rule in ("root", "last")}
    assert by_rule == {"root": (49, pytest.approx(1.7946826077040092, rel=1e-9)),
                       "last": (1, pytest.approx(0.006566050752050644, rel=1e-9))}


def strip_metadata(hlo_text: str) -> list[str]:
    """The module's instruction lines without their ``metadata={...}``."""
    return [re.sub(r", metadata=\{[^}]*\}", "", line)
            for line in hlo_text.splitlines() if " = " in line]


def _cut(seq_len):
    cell = spec.load_cell("qwen3-0.6b.1node.seq4k")
    cfg = dict(cell.config, hidden_size=256, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=64,
               intermediate_size=512, vocab_size=2048)
    return cfg, dict(cell.traffic, seq_len=seq_len, rows_per_node=1)


def _compile(cfg, mix):
    prog = program.build(spec.load_family(cfg).model_config(cfg), mix)
    params = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        prog.setup.abstract_params(), prog.param_shardings)
    rows = jax.ShapeDtypeStruct(
        (mix["nodes"], mix["rows_per_node"], mix["seq_len"]), jnp.int32,
        sharding=prog.batch_sharding)
    return jax.jit(prog.setup.train_step).lower(
        params, None, {"tokens": rows, "labels": rows}).compile().as_text()


# 2560 tokens take the chunked attention of the cells' 4096; 1024 the plain one
@pytest.fixture(scope="module", params=[2560, 1024])
def cut_on_cpu(request):
    """The cut's step compiled on the CPU with its scopes, and without them
    (``jax.named_scope`` made a no-op while the program traces)."""
    cfg, mix = _cut(request.param)
    scoped = _compile(cfg, mix)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        plain = _compile(cfg, mix)
    return scoped, plain


def test_scopes_are_metadata_only(cut_on_cpu):
    scoped, plain = cut_on_cpu
    assert "dsgd.grad" in scoped and "dsgd.grad" not in plain
    assert strip_metadata(scoped) == strip_metadata(plain)


def test_cut_step_holds_every_scope_and_phase(cut_on_cpu):
    """The scopes a one-node dense step passes through all reach the CPU
    compile's op_names, and so do its four phases. (XLA's CPU backend
    drops the metadata of the dots and reduce-windows it rewrites, so
    the coverage of every instruction is checked on the TPU compile.)"""
    op_names = scopes.op_scopes(cut_on_cpu[0]).values()
    found = set().union(*map(scopes.scope_names, op_names))
    assert set(scopes.SCOPES) - found == {scopes.GOSSIP, scopes.PROBES}
    assert {scopes.phase(n) for n in op_names} >= set(scopes.PHASES) - {"gossip",
                                                                         "probes"}
    assert {scopes.part(n) for n in op_names} >= {"sdpa", "lm_head"}


# ---------------------------------------------------------------------------
# Each cell's step for a described TPU v5e

# (configuration, traffic mix): the two cells, and the four-node mix kept
# out of the benchmark for now (its gossip rounds in bf16)
CASES = [("qwen3-0.6b", "1node.10x4k"), ("qwen2.5-14b.cut4", "1node.4x4k"),
         ("qwen3-0.6b", "4node.stlfw2")]
# instructions XLA puts in for itself: layout, tuples, arguments, and the
# TPU's buffer custom-calls
XLA_KINDS = {"copy", "copy-start", "copy-done", "bitcast", "tuple",
             "get-tuple-element", "parameter", "constant"}
XLA_CUSTOM_CALLS = {"AllocateBuffer", "ConcatBitcast"}
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_LINE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
_ARRAY = re.compile(r"\b(pred|[su](?:8|16|32|64)|bf16|f16|f32|f64)\[([\d,]*)\]")
_WIDTH = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
          "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}


@dataclasses.dataclass
class Inst:
    name: str
    opcode: str
    out_bytes: int
    calls: list
    target: str | None


def _module(hlo_text):
    """({computation: [Inst]}, the computations called from instructions
    (fused, reducers): those that the device runs as one operation)."""
    comps, nested, comp = collections.defaultdict(list), set(), None
    for line in hlo_text.splitlines():
        if " = " not in line and (m := _COMPUTATION.match(line)):
            comp = m.group(1)
            continue
        if not (m := _LINE.match(line)):
            continue
        name, shape, opcode = m.groups()
        calls = re.findall(r"calls=%?([\w.\-]+)", line)
        nested.update(calls)
        if opcode != "call":
            nested.update(re.findall(r"to_apply=%?([\w.\-]+)", line))
        target = re.search(r'custom_call_target="([^"]*)"', line)
        comps[comp].append(Inst(
            name, opcode,
            sum(_WIDTH[t] * math.prod(int(d) for d in dims.split(",") if d)
                for t, dims in _ARRAY.findall(shape)),
            calls, target.group(1) if target else None))
    return comps, nested


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


@pytest.mark.parametrize("case", CASES, ids="-".join)
def test_every_operation_of_the_cell_step_has_a_phase(topo, case, monkeypatch):
    """Every matmul of the step (a dot, a convolution, or a fusion that holds
    one) and every custom-call but the TPU's buffer ones maps to a phase;
    the fusions that map to none (the RoPE and mask tables JAX hoists out
    of the differentiated layer scan, the label copy) write under 0.5 % of
    the bytes the step's fusions write. With gossip, every
    collective-permute lies under ``dsgd.gossip``."""
    cfg = json.loads((BENCH / "configs" / f"{case[0]}.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{case[1]}.json").read_text())
    n = mix["nodes"]
    mesh = Mesh(np.array(topo.devices[:n]).reshape(n, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    monkeypatch.setattr(program, "make_device_mesh", lambda d, m: mesh)
    text = _compile(cfg, mix)
    op_scopes = scopes.op_scopes(text)
    comps, nested = _module(text)
    by_name = {i.name: i for insts in comps.values() for i in insts}

    def holds_matmul(inst):
        return inst.opcode in ("dot", "convolution") or any(
            holds_matmul(i) for c in inst.calls for i in comps[c])

    top = [i for c, insts in comps.items() if c not in nested for i in insts
           if i.opcode not in XLA_KINDS and i.target not in XLA_CUSTOM_CALLS]
    phase = {i.name: scopes.phase(op_scopes.get(i.name, "")) for i in top}
    assert [i.name for i in top if holds_matmul(i) and phase[i.name] is None] == []
    assert [i.name for i in top
            if i.opcode == "custom-call" and phase[i.name] is None] == []
    fusions = [i for i in top if i.opcode == "fusion"]
    unscoped = sum(i.out_bytes for i in fusions if phase[i.name] is None)
    assert unscoped < 0.005 * sum(i.out_bytes for i in fusions)
    permutes = [name for name in by_name if name.startswith("collective-permute")]
    assert bool(permutes) == (n > 1)
    assert {scopes.phase(op_scopes.get(p, "")) for p in permutes} <= {"gossip"}
    # where op_scopes moves a fusion off XLA's own op_name: only the known
    # cases, among them each parameter's x[None] after the update or gossip
    moved = known_renames(text)
    assert any(scopes.phase(r[2]) == ("gossip" if n > 1 else "update")
               for r in moved.values())


def test_scopes_are_metadata_only_for_v5e(topo, monkeypatch):
    """As ``test_scopes_are_metadata_only``, compiled for a described v5e,
    where the matmul fusions keep their metadata: qwen3 at its published
    widths, cut to 2 layers and one row of 4096 tokens."""
    cell = spec.load_cell("qwen3-0.6b.1node.seq4k")
    cfg = dict(cell.config, num_hidden_layers=2)
    mix = dict(cell.traffic, rows_per_node=1)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    monkeypatch.setattr(program, "make_device_mesh", lambda d, m: mesh)
    scoped = _compile(cfg, mix)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _compile(cfg, mix)
    assert "dsgd.grad" in scoped and "dsgd.grad" not in plain
    assert strip_metadata(scoped) == strip_metadata(plain)


# ---------------------------------------------------------------------------
# The program's other scope placements and the compile cache


@pytest.mark.parametrize("seq_len", [1024, 4096])  # the plain and chunked attend
def test_mla_attend_is_under_sdpa(seq_len):
    """MLA's attend over the compressed latents sits under ``sdpa`` too, so
    ``attention_share`` reads it."""
    mla = MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                    v_head_dim=16)
    cfg = ModelConfig(name="t", arch_type="dense", num_layers=1, d_model=64,
                      num_heads=4, num_kv_heads=4, head_dim=16, d_ff=64,
                      vocab_size=64, mla=mla)
    params = attention.init_mla_attention(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((1, seq_len, cfg.d_model), jnp.float32)
    positions = jnp.arange(seq_len)[None]
    text = jax.jit(lambda p, x: attention.mla_attention(
        p, cfg, x, positions=positions)[0]).lower(params, x).compile().as_text()
    logits = [op for op in scopes.op_scopes(text).values() if "bqhd,bkhd->bhqk" in op]
    assert logits and all(scopes.part(op) == "sdpa" for op in logits)


def test_cache_entry_keeps_its_own_scopes(monkeypatch, tmp_path):
    """Under ``scopes.enable_cache`` two programs that differ only in
    a named scope get an entry each, so an executable loaded from the
    cache reports the scope it was traced with (a step keyed without its
    metadata would report the scopes, or none, of whichever build wrote
    the entry first)."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_compilation_cache_include_metadata_in_key",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    compilation_cache.reset_cache()
    try:
        scopes.enable_cache()
        # the described chip's fixture turns the cache off while it holds
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

        def compiled(scope):
            def f(x):
                with jax.named_scope(scope):
                    return jnp.sin(x) * 2.0

            return jax.jit(f).lower(np.ones(8, np.float32)).compile().as_text()

        assert "scope_a" in compiled("scope_a")
        assert "scope_b" in compiled("scope_b")
        assert len(list(tmp_path.iterdir())) >= 2
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
