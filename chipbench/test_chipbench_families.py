"""The model family seam: what ``families/qwen.py`` took over gives what
the harness gave before, a family is found by its configuration's class,
a new family lands as files alone, and a run hands its readers the
cell's configuration, traffic and, traced, the step's op_names. On the
CPU."""

import dataclasses
import gzip
import hashlib
import json
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, harness, program, reduce, reference, scopes, spec, weights
from chipbench.test_chipbench_control import small

ROOT = Path(__file__).resolve().parent.parent
DATA = ROOT / "chipbench" / "testdata"
CELLS = ["qwen3-0.6b.1node.seq4k", "qwen2.5-14b.cut4.1node.seq4k"]
SEED = 2**31 + 7
ROWS = jax.random.PRNGKey(3)  # the reference step's tokens: (1, 2, 1025)


def digest(x) -> str:
    a = np.asarray(x)
    return hashlib.sha256(f"{a.dtype} {a.shape}".encode() + a.tobytes()).hexdigest()[:16]


# recorded from the harness before the family seam: at each cell's full
# configuration from shapes alone; at test_chipbench_control.small's cut,
# digests (``digest``) of the weights made from SEED and of the
# reference's first step on ROWS, and its loss in hex
PINNED = {
    "qwen3-0.6b.1node.seq4k": {
        "shapes": {
            "embed": ((151936, 1024), "embed"),
            "final_norm": ((1024,), "norm"),
            "k_norm": ((28, 128), "norm"),
            "ln1": ((28, 1024), "norm"),
            "ln2": ((28, 1024), "norm"),
            "q_norm": ((28, 128), "norm"),
            "w_down": ((28, 3072, 1024), "matrix"),
            "w_gate": ((28, 1024, 3072), "matrix"),
            "w_up": ((28, 1024, 3072), "matrix"),
            "wk": ((28, 1024, 1024), "matrix"),
            "wo": ((28, 2048, 1024), "matrix"),
            "wq": ((28, 1024, 2048), "matrix"),
            "wv": ((28, 1024, 1024), "matrix"),
        },
        "tree": {
            "['embed']['table']": ((1, 151936, 1024), "bfloat16"),
            "['final_norm']['scale']": ((1, 1024), "bfloat16"),
            "['stages'][0]['attn']['k_norm']['scale']": ((1, 28, 128), "bfloat16"),
            "['stages'][0]['attn']['q_norm']['scale']": ((1, 28, 128), "bfloat16"),
            "['stages'][0]['attn']['wk']": ((1, 28, 1024, 1024), "bfloat16"),
            "['stages'][0]['attn']['wo']": ((1, 28, 2048, 1024), "bfloat16"),
            "['stages'][0]['attn']['wq']": ((1, 28, 1024, 2048), "bfloat16"),
            "['stages'][0]['attn']['wv']": ((1, 28, 1024, 1024), "bfloat16"),
            "['stages'][0]['ln1']['scale']": ((1, 28, 1024), "bfloat16"),
            "['stages'][0]['ln2']['scale']": ((1, 28, 1024), "bfloat16"),
            "['stages'][0]['mlp']['w_down']": ((1, 28, 3072, 1024), "bfloat16"),
            "['stages'][0]['mlp']['w_gate']": ((1, 28, 1024, 3072), "bfloat16"),
            "['stages'][0]['mlp']['w_up']": ((1, 28, 1024, 3072), "bfloat16"),
        },
        "step_flops": 204193482670080.0,
        "init": {
            "embed": "c991a8f671ed8c01", "final_norm": "63a86b0ed4d06254", "k_norm": "708de3119a888e0a",
            "ln1": "93367cceac9ea1a0", "ln2": "e5d838964f657e67", "q_norm": "a94a7b7959a19de1",
            "w_down": "8f3930904adb807d", "w_gate": "497b27ce179ba110", "w_up": "08ea703ea98e20f0",
            "wk": "ee3c7cf2a38a5563", "wo": "9e3a38cfbc71dcc1", "wq": "fd17d65173e091b7",
            "wv": "4df88d328ed1bbba",
        },
        "grad_norms": {
            "embed": "8f0220b7f027b774", "final_norm": "d900e209c88c7cd4", "k_norm": "f3ea185317c2919e",
            "ln1": "d212c5551b1c30b2", "ln2": "c20aba159e2f6938", "q_norm": "0acc309dc4caeb2d",
            "w_down": "0d616abf032c449b", "w_gate": "7a20701673683cf8", "w_up": "2b479410c2fc1228",
            "wk": "bedba1882569ca87", "wo": "965dc8f710b3e68d", "wq": "4414de7bc020c939",
            "wv": "c71276780c8998be",
        },
        "new": {
            "embed": "292d1a795864c3be", "final_norm": "9a582a37a37051aa", "k_norm": "40d0e0620ad7bc06",
            "ln1": "2012f099ff9bdfb6", "ln2": "1f6e48683510811d", "q_norm": "43c18ef3f5a838b8",
            "w_down": "011edbf9840c8cca", "w_gate": "f6815f8d992beb62", "w_up": "f66e58af66593a79",
            "wk": "536c76cb4e38dccd", "wo": "0936cbd0d797b95e", "wq": "f3cb253a7596179c",
            "wv": "0946b47bc788859c",
        },
        "loss": "0x1.ea62b80000000p+2",
    },
    "qwen2.5-14b.cut4.1node.seq4k": {
        "shapes": {
            "bk": ((4, 1024), "bias"),
            "bq": ((4, 5120), "bias"),
            "bv": ((4, 1024), "bias"),
            "embed": ((19008, 5120), "embed"),
            "final_norm": ((5120,), "norm"),
            "lm_head": ((5120, 19008), "matrix"),
            "ln1": ((4, 5120), "norm"),
            "ln2": ((4, 5120), "norm"),
            "w_down": ((4, 13824, 5120), "matrix"),
            "w_gate": ((4, 5120, 13824), "matrix"),
            "w_up": ((4, 5120, 13824), "matrix"),
            "wk": ((4, 5120, 1024), "matrix"),
            "wo": ((4, 5120, 5120), "matrix"),
            "wq": ((4, 5120, 5120), "matrix"),
            "wv": ((4, 5120, 1024), "matrix"),
        },
        "tree": {
            "['embed']['table']": ((1, 19008, 5120), "bfloat16"),
            "['embed']['unembed']": ((1, 5120, 19008), "bfloat16"),
            "['final_norm']['scale']": ((1, 5120), "bfloat16"),
            "['stages'][0]['attn']['bk']": ((1, 4, 1024), "bfloat16"),
            "['stages'][0]['attn']['bq']": ((1, 4, 5120), "bfloat16"),
            "['stages'][0]['attn']['bv']": ((1, 4, 1024), "bfloat16"),
            "['stages'][0]['attn']['wk']": ((1, 4, 5120, 1024), "bfloat16"),
            "['stages'][0]['attn']['wo']": ((1, 4, 5120, 5120), "bfloat16"),
            "['stages'][0]['attn']['wq']": ((1, 4, 5120, 5120), "bfloat16"),
            "['stages'][0]['attn']['wv']": ((1, 4, 5120, 1024), "bfloat16"),
            "['stages'][0]['ln1']['scale']": ((1, 4, 5120), "bfloat16"),
            "['stages'][0]['ln2']['scale']": ((1, 4, 5120), "bfloat16"),
            "['stages'][0]['mlp']['w_down']": ((1, 4, 13824, 5120), "bfloat16"),
            "['stages'][0]['mlp']['w_gate']": ((1, 4, 5120, 13824), "bfloat16"),
            "['stages'][0]['mlp']['w_up']": ((1, 4, 5120, 13824), "bfloat16"),
        },
        "step_flops": 126046552719360.0,
        "init": {
            "bk": "6096be6910c36a64", "bq": "a95e9c0a32ad777f", "bv": "7db705f69e6645d1",
            "embed": "400fe04a55ed6d2f", "final_norm": "84a9b9b50b7af404", "lm_head": "a7f261f6c82e1685",
            "ln1": "464980b48a033365", "ln2": "50cfa62d2127ddba", "w_down": "c7ae49a6affa381a",
            "w_gate": "798bc552efa75976", "w_up": "1676d0b7a3fa4b09", "wk": "b79d13c7112960d8",
            "wo": "eb2b121c2a950df2", "wq": "c1e1a03e8af4f24b", "wv": "9225bd568f57f1b4",
        },
        "grad_norms": {
            "bk": "9e01d42ddadee9bc", "bq": "fe6a7fc31175f619", "bv": "c7d46510a7c7c97e",
            "embed": "153f69873700baeb", "final_norm": "c60a570d47578e06", "lm_head": "c65c153a0a7e579e",
            "ln1": "4ba8d56ccdfd0977", "ln2": "737bced7d6367990", "w_down": "00acc0db2d456a75",
            "w_gate": "74ba517b83a52566", "w_up": "5edc3757f1e55d95", "wk": "a6eb00f92e0803f7",
            "wo": "8579babe94ca06b3", "wq": "9b3aaec7e870fa06", "wv": "d4062ba4c5f1e50e",
        },
        "new": {
            "bk": "4d599524fd080083", "bq": "45704bd43430356a", "bv": "c32c8455cb572ace",
            "embed": "5f84df893e980a6f", "final_norm": "6241485a52dada0b", "lm_head": "c343a1a821bb08ab",
            "ln1": "796c287879c8686e", "ln2": "050131eed7194f9d", "w_down": "1644bfeebf8877a7",
            "w_gate": "518b883cfce77f6e", "w_up": "3855a173ea1de8b9", "wk": "8cf36310e004209b",
            "wo": "b5f9c97498d74e38", "wq": "2597dd3671dc3ade", "wv": "9cacfc19029d57ab",
        },
        "loss": "0x1.04e7c00000000p+3",
    },
}


@pytest.fixture
def on_cpu(monkeypatch):
    monkeypatch.setattr(harness, "check_devices", lambda chips: jax.devices())
    monkeypatch.setattr(spec, "load_peaks", lambda kind: {"bf16_flops_per_s": None})


@pytest.mark.parametrize("name", CELLS)
def test_cell_names_its_family_by_class(name):
    cell = spec.load_cell(name)
    assert cell.config["architectures"][0] in cell.family.ARCHITECTURES
    assert set(cell.family.paths(cell.config)) == set(cell.family.shapes(cell.config))


@pytest.mark.parametrize("name", CELLS)
def test_weight_shapes_are_pinned(name):
    cell = spec.load_cell(name)
    assert dict(sorted(cell.family.shapes(cell.config).items())) == PINNED[name]["shapes"]


@pytest.mark.parametrize("name", CELLS)
def test_program_tree_is_pinned(name):
    """``to_program``'s tree from the family's weights and paths, at the
    cell's full size, by shapes alone."""
    cell = spec.load_cell(name)
    cfg, fam, n = cell.config, cell.family, cell.traffic["nodes"]
    setup = program.build(fam.model_config(cfg), cell.traffic).setup
    tree = jax.eval_shape(lambda k: program.to_program(program.broadcast_nodes(
        weights.init(k, cfg, fam), n), setup, fam.paths(cfg)), jax.random.PRNGKey(0))
    got = {jax.tree_util.keystr(p): (v.shape, str(v.dtype))
           for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert got == PINNED[name]["tree"]


@pytest.mark.parametrize("name", CELLS)
def test_step_flops_are_pinned(name):
    cell = spec.load_cell(name)
    assert flops.step_flops(cell.config, cell.traffic, cell.family) == \
        PINNED[name]["step_flops"]


@pytest.mark.parametrize("name", CELLS)
def test_small_cut_weights_are_pinned(name):
    cell = small(name)
    w = jax.jit(lambda k: weights.init(k, cell.config, cell.family))(
        weights.seed_key(SEED, weights.STREAM_WEIGHTS))
    assert {k: digest(v) for k, v in sorted(w.items())} == PINNED[name]["init"]


@pytest.mark.parametrize("name", CELLS)
def test_small_cut_reference_step_is_pinned(name):
    """The reference's first step, bit for bit: its loss, each leaf's
    float32 gradient norms and the new weights."""
    cell = small(name)
    cfg = cell.config
    theta = program.broadcast_nodes(jax.jit(lambda k: weights.init(k, cfg, cell.family))(
        weights.seed_key(SEED, weights.STREAM_WEIGHTS)), 1)
    batch = program.place_batch(jax.random.randint(ROWS, (1, 2, 1025), 0, cfg["vocab_size"]))
    step = jax.jit(reference.make_step(cfg, 0.005, cell.family))
    new, loss, norms = step(theta, batch, jnp.ones((1, 1), jnp.float32))
    assert float(loss).hex() == PINNED[name]["loss"]
    assert {k: digest(v) for k, v in sorted(norms.items())} == PINNED[name]["grad_norms"]
    assert {k: digest(v) for k, v in sorted(new.items())} == PINNED[name]["new"]


def _copy(tmp_path) -> dict:
    """A copy of the benchmark's files; returns its BENCHMARK.json."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _unchanged_but(tmp_path, added):
    """Every file of the copy but ``added`` is the repository's, byte for byte."""
    for path in (tmp_path / "chipbench").rglob("*"):
        rel = path.relative_to(tmp_path)
        if path.is_file() and "__pycache__" not in rel.parts and str(rel) not in added:
            assert path.read_bytes() == (ROOT / rel).read_bytes(), rel


TOY = "toy-2l"
TOY_CELL = "toy-2l.1node.2x1k"
TOY_FAMILY = '''"""A family that re-exports the dense decoder under a class of its own."""

from chipbench.families.qwen import *  # noqa: F401,F403

ARCHITECTURES = ("ToyForCausalLM",)
'''


def test_new_family_is_picked_up_without_editing(tmp_path, on_cpu):
    """A later change adds a model family as files: the family, a
    configuration naming its class, a traffic mix, limits, and the
    configuration's and cell's entries. The harness runs it, correct."""
    bench = _copy(tmp_path)
    base = small(CELLS[0])
    files = {
        "chipbench/families/toy.py": TOY_FAMILY,
        f"chipbench/configs/{TOY}.json": json.dumps(
            dict(base.config, architectures=["ToyForCausalLM"])),
        "chipbench/traffic/1node.2x1k.json": json.dumps(base.traffic),
        f"chipbench/limits/{TOY_CELL}.json": json.dumps(base.limits),
    }
    for rel, text in files.items():
        (tmp_path / rel).write_text(text)
    bench["configs"].append({"name": TOY, "source": "https://example.org/toy",
                             "file": f"chipbench/configs/{TOY}.json", "reduced": [],
                             "why": "the dense decoder under another class"})
    bench["workloads"].append({"name": TOY_CELL, "config": TOY, "traffic": "1node.2x1k",
                               "chips": 1, "why": "a new family's cell"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    _unchanged_but(tmp_path, files)
    assert dict(bench, configs=bench["configs"][:-1], workloads=bench["workloads"][:-1]) == \
        json.loads((ROOT / "BENCHMARK.json").read_text())

    cell = spec.load_cell(TOY_CELL, root=tmp_path)
    assert cell.family.ARCHITECTURES == ("ToyForCausalLM",)
    assert cell.family.shapes(cell.config) == base.family.shapes(base.config)
    result = harness.run(cell, SEED, 1.0, False, time.perf_counter())
    assert result["correct"], result["checks"]


@pytest.mark.parametrize("claims, count", [([], 0), (["Qwen3ForCausalLM"], 2)])
def test_a_class_claimed_by_no_family_or_two_is_refused(tmp_path, claims, count):
    """A configuration whose class no family claims, or two families do,
    names the classes the families claim and does not load."""
    bench = _copy(tmp_path)
    cfg = dict(spec.load_cell(CELLS[0]).config)
    if claims:
        (tmp_path / "chipbench" / "families" / "twin.py").write_text(
            f"ARCHITECTURES = {tuple(claims)!r}\n")
    else:
        cfg["architectures"] = ["NoSuchForCausalLM"]
    bench_dir = tmp_path / "chipbench"
    with pytest.raises(ValueError, match=f"{count} families claim it.*Qwen2ForCausalLM"):
        spec.load_family(cfg, bench_dir)


def test_traced_run_records_the_step_op_names(on_cpu):
    """A traced run hands its readers the step's op_names, the cell's
    configuration and its traffic; an untraced one no op_names."""
    seen = []
    capture = (spec.Metric("capture", "-", lambda rec: seen.append(rec)),)
    cell = dataclasses.replace(small(CELLS[0]), per_layer=capture, end_to_end=capture)
    harness.run(cell, SEED, 1.0, True, time.perf_counter())
    harness.run(cell, SEED, 1.0, False, time.perf_counter())
    traced, plain = seen
    assert traced.config == cell.config and traced.traffic == cell.traffic
    assert any(scopes.GRAD in scopes.scope_names(op) for op in traced.op_scopes.values())
    assert plain.op_scopes is None and plain.config == cell.config


MLP_SHARE = '''"""Own time of the device operations under the step's ``mlp`` scope, in %
of the traced window (device trace, the step's op_names)."""

from chipbench import scopes


def read(rec):
    if rec.trace is None or not rec.trace.devices or not rec.op_scopes:
        return None
    own = scopes.own_time(rec.trace)
    return 100.0 * sum(v for name, v in own.items()
                       if "mlp" in scopes.scope_names(rec.op_scopes.get(name, "")))
'''


def test_new_scope_reader_reads_a_recorded_trace(tmp_path):
    """A reader added as a file reads a scope's share from the record:
    on the trace and step recorded on the chip, ``scope_shares``' value."""
    bench = _copy(tmp_path)
    (tmp_path / "chipbench" / "metrics" / "mlp_share.py").write_text(MLP_SHARE)
    bench["per_layer"].append({
        "name": "mlp_share", "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "step program", "moves": "tokens_per_s_per_chip", "workloads": [CELLS[0]]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell(CELLS[0], root=tmp_path)
    read = {m.name: m for m in cell.per_layer}["mlp_share"].read
    tr = reduce.load(DATA / "tpu_scoped_2x1.xplane.pb")
    with gzip.open(DATA / "tpu_scoped_2x1.hlo.txt.gz", "rt") as f:
        op_scopes = scopes.op_scopes(f.read())
    rec = harness.RunRecord(
        chips=1, setup_s=1.0, step_s=[1.0], window_s=1.0, tokens_per_step=1,
        flops_per_step=1.0, peak_flops=None, config=cell.config, traffic=cell.traffic,
        trace=tr, op_scopes=op_scopes)
    want = scopes.scope_shares(tr, op_scopes)["mlp"]
    assert want > 0
    assert read(rec) == pytest.approx(want, rel=1e-12)
    assert read(dataclasses.replace(rec, op_scopes=None)) is None
