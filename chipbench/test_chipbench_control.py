"""``correct`` can fail: the control, and the program broken underneath.

At a size a test run can hold (a 2-layer cut of each cell's model at
its own layout, two 1,024-token rows), on the CPU, against each cell's own
limits. The control is the reference computed in float8 put in the
program's place; the faults break the timed path below the harness,
which then runs as it does on the chip, with its look for a chip and
its table of peaks patched to take the CPU.
"""

import dataclasses
import time

import jax
import pytest

from chipbench import compare, harness, program, reference, spec

CELLS = ["qwen3-0.6b.1node.seq4k", "qwen2.5-14b.cut4.1node.seq4k"]
SEEDS = [2**31 + 1, 2**31 + 2, 2**31 + 3]


def small(name):
    cell = spec.load_cell(name)
    cfg = dict(cell.config, hidden_size=256, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=64,
               intermediate_size=512, vocab_size=2048)
    mix = dict(cell.traffic, seq_len=1024, rows_per_node=2, trace_seconds=1)
    return dataclasses.replace(cell, config=cfg, traffic=mix)


@pytest.fixture(autouse=True)
def on_cpu(monkeypatch):
    monkeypatch.setattr(harness, "check_devices", lambda chips: jax.devices())
    monkeypatch.setattr(spec, "load_peaks", lambda kind: {"bf16_flops_per_s": None})


def _run(cell, seed=SEEDS[0]):
    return harness.run(cell, seed, 1.0, False, time.perf_counter())


@pytest.mark.parametrize("name", CELLS)
def test_sound_program_is_correct(name):
    result = _run(small(name))
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) <= set(result["readings"])


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = small(name)
    devices = jax.devices()[:1]
    bench = harness.Bench(cell, devices)
    f32 = harness.Reference(cell, devices)
    fp8 = harness.Reference(cell, devices, mm=reference.fp8_mm)
    for seed in SEEDS:
        _, batches, _ = bench.start(seed)
        batches = [jax.device_get(b) for b in batches]
        values = compare.gaps(fp8.readings(seed, batches), f32.readings(seed, batches))
        correct, checks = compare.judge(values, cell.limits)
        assert not correct, (seed, checks)


def _break_step(monkeypatch, fault):
    make = program.make_train_setup

    def broken_setup(*args, **kwargs):
        setup = make(*args, **kwargs)
        step = setup.train_step

        def train_step(params, opt, batch):
            new, new_opt, loss = step(params, opt, batch)
            return (params, opt, loss) if fault == "unchanged" else (new, new_opt, loss)

        return dataclasses.replace(setup, train_step=train_step)

    monkeypatch.setattr(program, "make_train_setup", broken_setup)


@pytest.mark.parametrize("name", CELLS)
def test_step_that_returns_its_state_unchanged_is_not_correct(name, monkeypatch):
    _break_step(monkeypatch, "unchanged")
    result = _run(small(name))
    assert not result["correct"]
    assert result["checks"]["grad"]["value"] > 0.99


@pytest.mark.parametrize("name", CELLS)
def test_half_the_batch_left_out_is_not_correct(name, monkeypatch):
    from repro.models import registry

    loss_fn = registry.loss_fn

    def half_loss(params, cfg, batch, impl="xla"):
        s = batch["tokens"].shape[-1] // 2
        return loss_fn(params, cfg, {k: v[..., :s] for k, v in batch.items()}, impl)

    monkeypatch.setattr(registry, "loss_fn", half_loss)
    assert not _run(small(name))["correct"]
