"""Plain reference of the served dense decoder (``"reference": "decoder"``):
the forward pass in float32 ``jax.numpy`` at the highest matmul precision,
one layer at a time, with no kernel, cache or batching, written from the
configuration file alone.

It follows the model as the configuration states it, departures included:
RMSNorm, rotary position embedding over the whole head (rotate-half
layout), grouped-query attention and a gated SiLU MLP in every layer of
``params["layers"]``.  It has no mixture of experts, no latent attention
and no leading dense layers: a configuration with those names a reference
of its own, ``bench/reference/<name>.py`` with this module's ``hidden`` and
``logits_rows``.  The weights are the tree the benchmark made from the
seed, read by name; a factored leaf ``{"a", "b"}`` applies as
``(x @ a) @ b``.

``precision="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 with a per-tensor (weights) or per-row (activations) absmax
scale, and the products accumulate in float32.  It is the step down from
the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8_MAX = 448.0


def _fp8(x, axis=None):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(x, w, fp8: bool):
    if fp8:
        x, w = _fp8(x, axis=-1), _fp8(w)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _linear(p, x, fp8):
    if isinstance(p, dict):
        return _mm(_mm(x, p["a"], fp8), p["b"], fp8)
    return _mm(x, p, fp8)


def _rmsnorm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    S, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(p, h, c, fp8):
    S = h.shape[0]
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    q = _rope(_linear(p["wq"], h, fp8).reshape(S, H, hd), c["rope_theta"])
    k = _rope(_linear(p["wk"], h, fp8).reshape(S, KV, hd), c["rope_theta"])
    v = _linear(p["wv"], h, fp8).reshape(S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)  # head i reads kv head i // (H // KV)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=jax.lax.Precision.HIGHEST) * hd**-0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", a, v, precision=jax.lax.Precision.HIGHEST)
    return _linear(p["wo"], o.reshape(S, H * hd), fp8)


def _mlp(p, h, fp8):
    g = _linear(p["w_gate"], h, fp8)
    return _linear(p["w_down"], jax.nn.silu(g) * _linear(p["w_up"], h, fp8), fp8)


@functools.partial(jax.jit, static_argnames=("c", "fp8"))
def _layer(layers, i, x, c, fp8):
    """Layer ``i`` of the stacked tree, sliced and cast to float32 here, so
    only one layer is ever held in float32."""
    lp = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False).astype(F32), layers)
    cd = dict(c)
    eps = cd["norm_eps"]
    x = x + _attention(lp["attn"], _rmsnorm(lp["attn_norm"]["scale"], x, eps), cd, fp8)
    return x + _mlp(lp["mlp"], _rmsnorm(lp["mlp_norm"]["scale"], x, eps), fp8)


@functools.partial(jax.jit, static_argnames=("c", "fp8"))
def _head(head, scale, x, c, fp8):
    head = _f32(head)
    return _linear(head, _rmsnorm(scale.astype(F32), x, dict(c)["norm_eps"]), fp8)


def _static(config: dict):
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
            "norm_eps")
    return tuple((k, config[k]) for k in keys if k in config)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, F32), tree)


def hidden(params, tokens: np.ndarray, config: dict, *, fp8: bool = False, pad_to: int = 512):
    """Final-layer hidden states (before the final norm) of one sequence,
    padded at the end to a multiple of ``pad_to`` (causal, so padding changes
    nothing before it) to bound the number of compiled shapes."""
    c = _static(config)
    S = len(tokens)
    Sp = -(-S // pad_to) * pad_to
    ids = np.zeros(Sp, np.int32)
    ids[:S] = tokens
    x = jnp.take(params["embed"], jnp.asarray(ids), axis=0).astype(F32)
    n = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
    for i in range(n):
        x = _layer(params["layers"], jnp.int32(i), x, c, fp8)
    return x


def logits_rows(params, x, rows: np.ndarray, config: dict, *, fp8: bool = False, block: int = 256):
    """Yield (row indices, logits) for the given rows, ``block`` at a time."""
    c = _static(config)
    head, scale = params["lm_head"], params["final_norm"]["scale"]
    for i in range(0, len(rows), block):
        r = rows[i : i + block]
        rp = np.zeros(block, np.int32)
        rp[: len(r)] = r
        yield r, _head(head, scale, x[jnp.asarray(rp)], c, fp8)[: len(r)]
