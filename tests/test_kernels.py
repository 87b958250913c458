"""Per-kernel allclose suites against the pure-jnp oracles (interpret mode),
and the rule that picks interpret mode.

Shape/dtype sweeps as required: parametrized grids + hypothesis-driven
random shapes.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import default_interpret
from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.flash_attention.ops import block_for
from repro.kernels.gossip_mix import (
    gossip_mix,
    gossip_mix_ref,
    gossip_schedule,
    gossip_schedule_ref,
)
from repro.kernels.interpret import resolve_interpret
from repro.kernels.rglru_scan import rglru_scan


# ---------------------------------------------------------------------------
# gossip_mix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [2, 8, 16, 32])
@pytest.mark.parametrize("P", [2048, 4096])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gossip_mix_grid(n, P, dtype):
    rng = np.random.default_rng(n * P)
    theta = jnp.asarray(rng.normal(size=(n, P)), dtype)
    W = np.abs(rng.normal(size=(n, n)))
    W = jnp.asarray(W / W.sum(1, keepdims=True), dtype)
    out = gossip_mix(theta, W)
    ref = gossip_mix_ref(theta, W)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    assert out.dtype == theta.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 12), st.integers(10, 5000), st.integers(0, 99))
def test_gossip_mix_hypothesis(n, P, seed):
    rng = np.random.default_rng(seed)
    theta = jnp.asarray(rng.normal(size=(n, P)), jnp.float32)
    W = np.abs(rng.normal(size=(n, n))) + 0.01
    W = jnp.asarray(W / W.sum(1, keepdims=True), jnp.float32)
    out = gossip_mix(theta, W)
    ref = gossip_mix_ref(theta, W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_gossip_mix_identity():
    theta = jnp.asarray(np.random.default_rng(0).normal(size=(4, 2048)), jnp.float32)
    out = gossip_mix(theta, jnp.eye(4))
    np.testing.assert_allclose(np.asarray(out), np.asarray(theta), atol=1e-6)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

CASES = [
    # (B, S, H, Hkv, D, window, softcap)
    (1, 128, 2, 2, 64, None, 0.0),
    (2, 256, 4, 2, 64, None, 0.0),
    (1, 256, 4, 1, 128, None, 0.0),   # MQA
    (1, 256, 4, 4, 32, 64, 0.0),      # sliding window, padded head dim
    (1, 384, 2, 2, 128, None, 50.0),  # softcap (gemma2)
    (1, 128, 8, 4, 256, 128, 0.0),    # gemma-style 256 head dim + window
    (2, 512, 4, 2, 64, 100, 30.0),    # window + softcap + odd window
]


@pytest.mark.parametrize("B,S,H,Hkv,D,window,softcap", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(B, S, H, Hkv, D, window, softcap, dtype):
    rng = np.random.default_rng(hash((B, S, H, D)) % 2**31)
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dtype)
    out = flash_attention(
        q, k, v, causal=True, window=window, softcap=softcap,
        block_q=128, block_kv=128,
    )
    ref = flash_attention_ref(q, k, v, causal=True, window=window, softcap=softcap)
    tol = 2e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


@settings(max_examples=8, deadline=None)
@given(
    st.integers(1, 2),
    st.sampled_from([128, 256]),
    st.sampled_from([(2, 1), (2, 2), (4, 2)]),
    st.sampled_from([32, 64, 128]),
    st.integers(0, 999),
)
def test_flash_attention_hypothesis(B, S, heads, D, seed):
    H, Hkv = heads
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_kv=128)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)


def test_flash_attention_small_seq_fallback():
    # S < block_q routes to the reference path; result must still be exact
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 16, 2, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 16, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 16, 2, 64)), jnp.float32)
    out = flash_attention(q, k, v)
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_attention_blocks_by_shape():
    seqs = (16, 128, 300, 1024, 4096, 4608)
    assert [block_for(s, 128) for s in seqs] == [128, 128, 384, 1024, 1024, 512]
    assert [block_for(s, 256) for s in seqs] == [128, 128, 384, 512, 512, 512]
    assert block_for(4096, 64) == 1024  # padded to a 128 head dim
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 1536, 2, 128)), jnp.float32)
    out = flash_attention(q, q, q)  # three 512 blocks
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(flash_attention_ref(q, q, q)), atol=2e-3, rtol=2e-3
    )


GRAD_CASES = [
    # (S, H, Hkv, D, window, softcap)
    (256, 2, 2, 128, None, 0.0),   # groups 1
    (512, 4, 2, 128, None, 0.0),   # groups 2 (qwen3)
    (256, 5, 1, 128, None, 0.0),   # groups 5 (the 14B cut)
    (512, 2, 1, 128, 200, 0.0),    # window across blocks
    (256, 2, 2, 128, None, 30.0),  # softcap
    (256, 2, 1, 64, 100, 20.0),    # padded head dim, window and softcap
]


def _rel_err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _attention_grads(attend, q, k, v, w):
    """dq, dk, dv of sum(attend(q, k, v) * w)."""
    loss = lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32) * w)
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("S,H,Hkv,D,window,softcap", GRAD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_grad_vs_ref(S, H, Hkv, D, window, softcap, dtype):
    rng = np.random.default_rng(S * H + D)
    q = jnp.asarray(rng.normal(size=(1, S, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(1, S, Hkv, D)), dtype)
    v = jnp.asarray(rng.normal(size=(1, S, Hkv, D)), dtype)
    w = jnp.asarray(rng.normal(size=(1, S, H, D)), jnp.float32)
    opts = dict(causal=True, window=window, softcap=softcap)
    got = _attention_grads(
        lambda q, k, v: flash_attention(q, k, v, block_q=128, block_kv=128, **opts),
        q, k, v, w,
    )
    want = _attention_grads(
        lambda q, k, v: flash_attention_ref(q, k, v, **opts), q, k, v, w
    )
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    for name, g, r in zip("qkv", got, want):
        assert g.dtype == dtype and g.shape == r.shape, name
        assert _rel_err(g, r) < tol, (name, _rel_err(g, r))


def test_flash_attention_grad_under_vmap_over_nodes():
    """The dsgd_pod step vmaps the loss over nodes: the kernel's forward and
    backward batch along a leading node axis."""
    rng = np.random.default_rng(7)
    n, S, H, Hkv, D = 2, 256, 4, 2, 128
    q = jnp.asarray(rng.normal(size=(n, 1, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(n, 1, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(n, 1, S, Hkv, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(n, 1, S, H, D)), jnp.float32)
    kernel = lambda q, k, v: flash_attention(q, k, v, block_q=128, block_kv=128)
    got = jax.vmap(lambda *a: _attention_grads(kernel, *a))(q, k, v, w)
    for node in range(n):
        want = _attention_grads(flash_attention_ref, q[node], k[node], v[node], w[node])
        for name, g, r in zip("qkv", got, want):
            assert _rel_err(g[node], r) < 1e-5, (node, name)


# ---------------------------------------------------------------------------
# gossip_schedule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [5, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gossip_schedule_vs_ref(n, dtype):
    rng = np.random.default_rng(n)
    theta = jnp.asarray(rng.normal(size=(n, 4096)), dtype)
    perms = jnp.asarray(np.stack([rng.permutation(n) for _ in range(3)]), jnp.int32)
    coeffs = jnp.asarray([0.5, 0.3, 0.2], jnp.float32)
    out = gossip_schedule(theta, coeffs, perms)
    ref = gossip_schedule_ref(theta, coeffs, perms)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    assert out.dtype == theta.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


# ---------------------------------------------------------------------------
# interpret mode: one rule for every kernel
# ---------------------------------------------------------------------------

def test_default_interpret_only_on_cpu():
    assert jax.default_backend() == "cpu"
    assert default_interpret() is True
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False


def test_kernels_without_interpret_resolve_by_the_rule(monkeypatch):
    # the kernel modules (their packages export functions of the same name)
    fa_mod = importlib.import_module("repro.kernels.flash_attention.flash_attention")
    rg_mod = importlib.import_module("repro.kernels.rglru_scan.rglru_scan")

    seen = []

    def spy(interpret):
        seen.append(interpret)
        return resolve_interpret(interpret)

    monkeypatch.setattr(fa_mod, "resolve_interpret", spy)
    monkeypatch.setattr(rg_mod, "resolve_interpret", spy)
    jax.clear_caches()  # the jitted wrappers must trace again to reach the spy
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
    flash_attention(q, q, q)
    a = jnp.asarray(rng.uniform(0.5, 1.0, size=(1, 256, 512)), jnp.float32)
    rglru_scan(a, a)
    assert seen == [None, None]
