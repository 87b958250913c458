"""The harness as data, its traffic generator, and its refusal to run
without a TPU. All on the CPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import spec, traffic, weights

ROOT = Path(__file__).resolve().parent.parent
CELLS = ["qwen3-0.6b.1node.seq4k", "qwen2.5-14b.cut4.1node.seq4k"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_found_by_name(name):
    cell = spec.load_cell(name)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    w = {x["name"]: x for x in bench["workloads"]}[name]
    assert cell.config_name == w["config"] and cell.traffic_name == w["traffic"]
    assert cell.traffic["nodes"] == cell.chips
    assert {m.name for m in cell.end_to_end} >= {"setup_s", "tokens_per_s_per_chip"}
    assert "step_mfu" in {m.name for m in cell.per_layer}
    assert cell.limits and set(cell.limits) <= {"loss0", "loss1", "loss2", "grad",
                                                "grad_p90", "change"}


def test_new_metric_file_is_picked_up_without_editing(tmp_path):
    """A later change adds a metric by adding its reader and an entry."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "steps_traced", "unit": "steps", "better": "higher",
        "source": "device_trace", "layer": "step program",
        "moves": "tokens_per_s_per_chip", "workloads": [CELLS[0]]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "chipbench" / "metrics" / "steps_traced.py").write_text(
        "def read(rec):\n    return float(len(rec.step_s))\n")
    cell = spec.load_cell(CELLS[0], root=tmp_path)
    reader = {m.name: m for m in cell.per_layer}["steps_traced"].read
    assert reader(type("R", (), {"step_s": [0.1, 0.2]})()) == 2.0
    assert "steps_traced" not in {m.name for m in spec.load_cell(CELLS[1], tmp_path).per_layer}


@pytest.mark.parametrize("limits", [None, {"window_compiles": 0}])
def test_cell_without_reference_limits_is_refused(tmp_path, limits):
    """A cell whose limits file is missing, or compares nothing with the
    reference, would report correct with no comparison: it is refused."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = dict(bench["workloads"][0], name="qwen3-0.6b.unjudged")
    bench["workloads"].append(new)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    if limits is not None:
        (tmp_path / "chipbench" / "limits" / f"{new['name']}.json").write_text(
            json.dumps(limits))
    with pytest.raises((FileNotFoundError, ValueError)):
        spec.load_cell(new["name"], root=tmp_path)
    spec.load_cell(CELLS[0], root=tmp_path)


def _small_mix(**over):
    mix = dict(spec.load_cell(CELLS[0]).traffic)
    mix.update({"seq_len": 4095, "pool_batches": 2, **over})
    return mix


def test_token_pool_is_a_function_of_the_seed():
    mix = _small_mix()
    pool = jax.jit(traffic.make_pool_fn(mix, 512))
    a = pool(weights.seed_key(2**31 + 5, weights.STREAM_TRAFFIC))
    b = pool(weights.seed_key(2**31 + 5, weights.STREAM_TRAFFIC))
    c = pool(weights.seed_key(2**31 + 6, weights.STREAM_TRAFFIC))
    assert a.shape == (2, 1, mix["rows_per_node"], 4096) and a.dtype == np.int32
    np.testing.assert_array_equal(a, b)
    assert np.mean(np.asarray(a) != np.asarray(c)) > 0.5
    assert int(a.min()) >= 0 and int(a.max()) < 512


def test_token_frequencies_follow_the_corpus_distribution():
    """Each domain's empirical frequencies match DomainSkewCorpus's."""
    from repro.data.tokens import DomainSkewCorpus

    vocab, n_dom = 512, 4
    mix = _small_mix(nodes=n_dom, own_domain_share=1.0 - 1e-9, pool_batches=16)
    rows = np.asarray(jax.jit(traffic.make_pool_fn(mix, vocab))(
        weights.seed_key(11, weights.STREAM_TRAFFIC)))
    corpus = DomainSkewCorpus(vocab_size=vocab, n_domains=n_dom,
                              zipf_a=mix["zipf_a"], seed=mix["corpus_seed"])
    for k in range(n_dom):
        seen = np.bincount(rows[:, k].ravel(), minlength=vocab) / rows[:, k].size
        want = corpus.domain_probs(k)
        n = rows[:, k].size
        # every frequency within 5 binomial standard deviations (+1/n)
        sd = np.sqrt(want * (1 - want) / n)
        assert np.all(np.abs(seen - want) <= 5 * sd + 1.0 / n), k
        assert np.argmax(seen) == np.argmax(want)


def test_run_refuses_a_machine_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload", CELLS[0],
         "--seed", "0", "--seconds", "10", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_compiles_are_counted():
    from chipbench.harness import count_compiles

    double = jax.jit(lambda x: 2 * x)
    double(np.ones(3, np.float32))
    with count_compiles() as warm:
        double(np.ones(3, np.float32))
    with count_compiles() as cold:
        double(np.ones(5, np.float32))
    assert len(warm) == 0 and len(cold) >= 1


def test_seed_keys_take_large_seeds():
    a = weights.seed_key(2**31 + 3, 0)
    b = weights.seed_key(2**32 + 2**31 + 3, 0)
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))
    with pytest.raises(ValueError):
        weights.seed_key(-1, 0)
