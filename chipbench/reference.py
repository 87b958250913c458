"""Plain float32 reference of the D-SGD step, from the published equations.

Qwen2/Qwen3 decoder (HF ``modeling_qwen2`` / ``modeling_qwen3``): RMSNorm
(x * rsqrt(mean x^2 + eps) * w), grouped-query attention with optional
QKV bias and per-head q/k RMSNorm before rotary embedding (rotate-half,
theta from the config), causal softmax, SwiGLU MLP, final RMSNorm, LM
head (the embedding, transposed, when tied), mean next-token cross
entropy. D-SGD (Algorithm 1 of the paper): every node takes one SGD step
on its own rows, then theta_i <- sum_j W_ij theta_j.

Everything is computed in float32 at ``Precision.HIGHEST``; parameters
are stored in the configuration's dtype after every step, as the
configuration states. It imports nothing of the program. Memory is kept
in bounds by backpropagating layer by layer (``make_step``), one row of
tokens at a time, and by blocks of query rows and of loss rows, which
change no element's arithmetic.

``mm`` is the one place matmul operands are read. The control passes
``fp8_mm``: operands rounded to float8 (e4m3 forward, e5m2 gradients)
with a per-tensor scale, the next precision below bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
BLOCK = 512  # query rows per attention block and rows per loss block
WHOLE = ("embed", "lm_head", "final_norm")  # the leaves not stacked per layer


def f32_mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HI, preferred_element_type=jnp.float32)


def _quantize(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def fp8_mm(spec, a, b):
    e4 = jnp.float8_e4m3fn
    return f32_mm(spec, _quantize(a, e4), _quantize(b, e4))


def _fp8_fwd(spec, a, b):
    return fp8_mm(spec, a, b), (a, b)


def _fp8_bwd(spec, res, g):
    a, b = res
    e4, e5 = jnp.float8_e4m3fn, jnp.float8_e5m2
    qa, qb, qg = _quantize(a, e4), _quantize(b, e4), _quantize(g, e5)
    _, vjp = jax.vjp(lambda x, y: f32_mm(spec, x, y), qa, qb)
    return vjp(qg)


fp8_mm.defvjp(_fp8_fwd, _fp8_bwd)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (S, H, Dh) -> rotate-half rotary embedding at positions 0..S-1."""
    s, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(q, k, v, mm, blk):
    """Causal attention of one row. q: (S, H, Dh); k, v: (S, Hkv, Dh)."""
    s, h, dh = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        scores = mm("qhd,khd->hqk", qb, k) * dh**-0.5
        qpos = i * blk + jnp.arange(blk)
        scores = jnp.where(kpos[None, None, :] <= qpos[None, :, None], scores, -jnp.inf)
        return mm("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(s // blk))
    return out.reshape(s, h, dh)


def layer(x, p, cfg, mm):
    """One decoder layer on one row. x: (S, D) float32; p: its weights."""
    arch = cfg["implied_by_architecture"]
    eps, hd = cfg["rms_norm_eps"], cfg["head_dim"]
    s = x.shape[0]
    h = rms_norm(x, p["ln1"], eps)
    q, k, v = (mm("sd,df->sf", h, p[n]) for n in ("wq", "wk", "wv"))
    if arch["qkv_bias"]:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(s, -1, hd)
    k = k.reshape(s, -1, hd)
    v = v.reshape(s, -1, hd)
    if arch["qk_norm"]:
        q = rms_norm(q, p["q_norm"], eps)
        k = rms_norm(k, p["k_norm"], eps)
    theta = float(cfg["rope_theta"])
    a = attention(rope(q, theta), rope(k, theta), v, mm, min(BLOCK, s))
    x = x + mm("sf,fd->sd", a.reshape(s, -1), p["wo"])
    h = rms_norm(x, p["ln2"], eps)
    g = mm("sd,df->sf", h, p["w_gate"])
    u = mm("sd,df->sf", h, p["w_up"])
    return x + mm("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"])


def head_loss(x, final_norm, head, labels, keep, cfg, mm):
    """Summed cross entropy of one row over the positions ``keep`` marks.
    x: (S, D) the last layer's output; head: (D, V)."""
    x = rms_norm(x, final_norm, cfg["rms_norm_eps"])
    blk = min(BLOCK, x.shape[0])

    def block(i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * blk, blk, 0)
        lb = jax.lax.dynamic_slice_in_dim(labels, i * blk, blk, 0)
        kb = jax.lax.dynamic_slice_in_dim(keep, i * blk, blk, 0)
        logits = mm("sd,dv->sv", xb, head)
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, lb[:, None], -1)[:, 0]
        return jnp.sum(nll * kb)

    return jnp.sum(jax.lax.map(jax.checkpoint(block), jnp.arange(x.shape[0] // blk)))


def make_step(cfg: dict, lr: float, *, mm=f32_mm, half: bool = False):
    """``step(theta, batch, W) -> (theta_next, mean loss, grad norms)``.

    theta: benchmark layout with a leading node axis, stored dtype.
    batch: {"tokens", "labels"} (nodes, rows, S). W: (n, n) float32.
    grad norms: per leaf, (n,) or (n, L), of the float32 gradient.

    Backpropagation runs layer by layer by hand: the forward keeps each
    layer's input, and the backward recomputes one layer at a time,
    takes its float32 gradient, stores its mixed update and drops it. So
    only one layer's float32 weights and gradients are live at a time.
    ``half`` keeps only the first half of every row's positions: the
    fault "half of the batch left out, the mean taken over the rest".
    """
    dtype = jnp.dtype(cfg["torch_dtype"])
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    tied = cfg["tie_word_embeddings"]

    def mix(W, x):
        return jnp.einsum("ij,j...->i...", W, x, precision=HI)

    def step(theta, batch, W):
        tokens, labels = batch["tokens"], batch["labels"]  # (n, rows, S)
        n_rows, s = tokens.shape[1], tokens.shape[2]
        keep = (jnp.arange(s) < (s // 2 if half else s)).astype(jnp.float32)
        count = n_rows * jnp.sum(keep)
        names = sorted(k for k in theta if k not in WHOLE)
        n_layers = theta[names[0]].shape[1]

        def weights_of(l):  # layer l of every node, float32
            return {k: f32(jax.lax.dynamic_index_in_dim(theta[k], l, 1, False))
                    for k in names}

        # a node's rows through one layer, one row at a time; all nodes
        node_layer = lambda x, p: jax.lax.map(  # noqa: E731
            jax.checkpoint(lambda r: layer(r, p, cfg, mm)), x)
        all_layer = jax.vmap(node_layer)

        x0 = jax.vmap(lambda e, t: f32(e)[t])(theta["embed"], tokens)

        def fwd(x, l):
            return all_layer(x, weights_of(l)), x

        x_last, inputs = jax.lax.scan(fwd, x0, jnp.arange(n_layers))

        def node_head(x, fn, head, lab):
            return jnp.sum(jax.lax.map(
                lambda a: head_loss(a[0], fn, head, a[1], keep, cfg, mm), (x, lab))) / count

        head = (jnp.swapaxes(theta["embed"], 1, 2) if tied else theta["lm_head"])
        loss, (dx, d_fn, d_head) = jax.vmap(
            jax.value_and_grad(node_head, argnums=(0, 1, 2)))(
                x_last, f32(theta["final_norm"]), f32(head), labels)

        def bwd(carry, l):
            dx, out = carry
            p = weights_of(l)
            _, vjp = jax.vjp(all_layer, inputs[l], p)
            dx, dp = vjp(dx)
            out = {k: jax.lax.dynamic_update_index_in_dim(
                out[k], mix(W, p[k] - lr * dp[k]).astype(dtype), l, 1) for k in names}
            norms = {k: jnp.sqrt(jnp.sum(dp[k] ** 2, axis=tuple(range(1, dp[k].ndim))))
                     for k in names}
            return (dx, out), norms

        out0 = {k: jnp.zeros_like(theta[k]) for k in names}
        (dx0, new), layer_norms = jax.lax.scan(bwd, (dx, out0), jnp.arange(n_layers)[::-1])
        grad_norms = {k: jnp.swapaxes(v[::-1], 0, 1) for k, v in layer_norms.items()}

        # the embedding's gradient: the lookup's, plus the head's when tied
        d_embed = jax.vmap(lambda t, g: jnp.zeros((cfg["vocab_size"], g.shape[-1]),
                                                  jnp.float32).at[t.reshape(-1)].add(
                                                      g.reshape(-1, g.shape[-1])))(tokens, dx0)
        if tied:
            d_embed = d_embed + jnp.swapaxes(d_head, 1, 2)
        else:
            new["lm_head"] = mix(W, f32(theta["lm_head"]) - lr * d_head).astype(dtype)
            grad_norms["lm_head"] = jnp.sqrt(jnp.sum(d_head ** 2, axis=(1, 2)))
        new["embed"] = mix(W, f32(theta["embed"]) - lr * d_embed).astype(dtype)
        new["final_norm"] = mix(W, f32(theta["final_norm"]) - lr * d_fn).astype(dtype)
        grad_norms["embed"] = jnp.sqrt(jnp.sum(d_embed ** 2, axis=(1, 2)))
        grad_norms["final_norm"] = jnp.sqrt(jnp.sum(d_fn ** 2, axis=1))
        return new, jnp.mean(loss), grad_norms

    return step


def leaf_norms(tree: dict) -> dict:
    """Per-leaf Euclidean norms: (n,) for whole leaves, (n, L) for the
    stacked layer leaves (a leaf is one layer's tensor of one node)."""
    out = {}
    for k, x in tree.items():
        x = x.astype(jnp.float32)
        stacked = k not in WHOLE
        axes = tuple(range(2 if stacked else 1, x.ndim))
        out[k] = jnp.sqrt(jnp.sum(x * x, axis=axes))
    return out


def diff_norms(a: dict, b: dict, scale: float = 1.0) -> dict:
    """``leaf_norms(a - b) / scale`` without holding the difference tree."""
    return leaf_norms({k: (a[k].astype(jnp.float32) - b[k].astype(jnp.float32)) / scale
                       for k in a})
