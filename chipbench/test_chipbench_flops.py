"""Model FLOPs from shapes, and the table of peaks."""

import pytest

from chipbench import flops, spec


def test_qwen3_cell_model_flops():
    """20.4 TFLOP for each 4,096-token row of the step."""
    cell = spec.load_cell("qwen3-0.6b.1node.seq4k")
    rows = cell.traffic["rows_per_node"]
    assert flops.step_flops(cell.config, cell.traffic, cell.family) == pytest.approx(
        rows * 20.4e12, rel=0.01)


def test_qwen25_cut_model_flops():
    """31.5 TFLOP for each 4,096-token row of the step."""
    cell = spec.load_cell("qwen2.5-14b.cut4.1node.seq4k")
    rows = cell.traffic["rows_per_node"]
    assert cell.family.matmul_params(cell.config) == pytest.approx(1.198e9, rel=0.001)
    assert flops.step_flops(cell.config, cell.traffic, cell.family) == pytest.approx(
        rows * 31.5e12, rel=0.01)


def test_untied_embedding_adds_no_flops():
    """Untying adds a lookup table, not a matmul: the head is counted once
    either way."""
    cell = spec.load_cell("qwen3-0.6b.1node.seq4k")
    cfg = dict(cell.config)
    tied = cell.family.matmul_params(cfg)
    cfg["tie_word_embeddings"] = False
    assert cell.family.matmul_params(cfg) == tied


def test_flops_scale_with_nodes():
    one = spec.load_cell("qwen3-0.6b.1node.seq4k")
    four = dict(spec.load_traffic("4node.stlfw2"),
                rows_per_node=one.traffic["rows_per_node"])
    assert flops.step_flops(one.config, four, one.family) == pytest.approx(
        4 * flops.step_flops(one.config, one.traffic, one.family))


def test_peaks_by_device_kind():
    assert spec.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        spec.load_peaks("TPU v99")
