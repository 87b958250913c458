"""The Pallas flash-attention kernel inside the model (CPU, interpret mode).

A small model with qwen3's attention options trains through the kernel to
the same loss and gradients as through XLA, and the default ``impl`` picks
the kernel only where the selection rule of ``models/attention.attention``
says: a TPU backend, a causal full sequence above 2048 tokens, and a head
dim that is a multiple of 128.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import registry


def _qwen3_small(**over):
    """2 layers of qwen3's attention: qk-norm, GQA groups 2, d_head 128."""
    shape = dict(num_layers=2, d_model=256, num_heads=2, num_kv_heads=1,
                 head_dim=128, d_ff=512, vocab_size=512, dtype="float32")
    return dataclasses.replace(get_config("qwen3-0.6b"), **{**shape, **over})


def test_qwen3_attention_through_kernel_matches_xla():
    cfg = _qwen3_small()
    assert cfg.qk_norm
    params = registry.init_model(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 512), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

    def loss_and_grads(impl):
        loss = lambda p: registry.loss_fn(p, cfg, batch, impl=impl)[0]
        return jax.value_and_grad(loss)(params)

    loss_k, grads_k = loss_and_grads("pallas")
    loss_x, grads_x = loss_and_grads("xla")
    assert abs(float(loss_k) - float(loss_x)) <= 1e-4 * abs(float(loss_x))
    leaves_k = jax.tree_util.tree_leaves_with_path(grads_k)
    leaves_x = jax.tree_util.tree_leaves(grads_x)
    assert len(leaves_k) == len(leaves_x)
    for (path, gk), gx in zip(leaves_k, leaves_x):
        nk, nx = float(jnp.linalg.norm(gk)), float(jnp.linalg.norm(gx))
        assert abs(nk - nx) <= 1e-2 * nx, (jax.tree_util.keystr(path), nk, nx)


@pytest.fixture
def fresh_traces():
    """The kernel's wrappers resolve interpret mode while tracing: drop
    cached traces before and after a test that steers the backend."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _step_text(cfg, seq, platforms=None):
    """The gradient of the default-``impl`` loss, lowered (not compiled)."""
    params = jax.eval_shape(lambda: registry.init_model(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    grad = jax.jit(jax.grad(
        lambda p, t: registry.loss_fn(p, cfg, {"tokens": t, "labels": t})[0]
    ))
    traced = grad.trace(params, tokens)
    lowered = traced.lower(lowering_platforms=platforms) if platforms else traced.lower()
    return lowered.as_text()


@pytest.mark.parametrize("head_dim,kernel", [(128, True), (64, False)])
def test_default_impl_takes_the_kernel_only_on_a_tpu(fresh_traces, monkeypatch,
                                                     head_dim, kernel):
    cfg = _qwen3_small(head_dim=head_dim)
    # on the CPU the chunked XLA attention stays
    assert "tpu_custom_call" not in _step_text(cfg, 4096)
    # on a TPU backend: the three kernels where the head dim fills the lanes
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _step_text(cfg, 4096, platforms=("tpu",))
    names = {n for n in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq") if n in text}
    assert ("tpu_custom_call" in text) == kernel
    assert names == ({"flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"} if kernel else set())
    # and at 2048 tokens the dense XLA attention stays on any backend
    assert "tpu_custom_call" not in _step_text(cfg, 2048, platforms=("tpu",))

