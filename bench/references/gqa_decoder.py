"""Plain reference of a pre-norm decoder with grouped-query attention.

The Llama layer as Phi-3 and Yi publish it: RMSNorm before attention and
before the MLP, rotary embeddings on the two halves of each head
(``rotate_half``), causal softmax attention scaled by ``head_dim ** -0.5``
with ``num_key_value_heads`` shared by groups of query heads, a SiLU-gated
MLP, a final RMSNorm and an untied output head.  Written in plain
``jax.numpy`` in float32 at ``highest`` matmul precision, with no kernel,
cache or batching of the system under test, and importing nothing of it.

Weights are random, made from the seed one layer at a time by
:func:`layer_weights`, so the reference rebuilds layer ``l`` on its own
and gets the same values the benchmark handed to the program.

The model's counts, which the roofline and ``mfu`` readers take from a
configuration's reference, are :mod:`bench.flops`' dense GQA formulas.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import (attention_bytes, attention_flops,  # noqa: F401
                         model_flops)

STD = 0.02                     # initializer_range of both published configs


class Sizes(NamedTuple):
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    eps: float


def sizes_of(conf: dict) -> Sizes:
    """Sizes from a configuration file's Hugging Face keys."""
    heads = conf["num_attention_heads"]
    return Sizes(layers=conf["num_hidden_layers"], d=conf["hidden_size"],
                 heads=heads, kv_heads=conf["num_key_value_heads"],
                 head_dim=conf.get("head_dim")
                 or conf["hidden_size"] // heads,
                 d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
                 rope_theta=float(conf["rope_theta"]),
                 eps=float(conf["rms_norm_eps"]))


# -- weights ---------------------------------------------------------------
def _normal(key, shape):
    return (jax.random.normal(key, shape, jnp.float32) * STD).astype(
        jnp.bfloat16)


def _norm_scale(key, d):
    return 1.0 + 0.1 * jax.random.normal(key, (d,), jnp.float32)


def layer_weights(s: Sizes, key, l) -> Dict[str, jax.Array]:
    """Layer ``l``'s weights, bf16 matrices ``[in, out]`` and f32 norm
    scales, from ``fold_in(key, l)``."""
    k = jax.random.split(jax.random.fold_in(key, l), 9)
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    return {"wq": _normal(k[0], (s.d, q)), "wk": _normal(k[1], (s.d, kv)),
            "wv": _normal(k[2], (s.d, kv)), "wo": _normal(k[3], (q, s.d)),
            "wg": _normal(k[4], (s.d, s.d_ff)),
            "wi": _normal(k[5], (s.d, s.d_ff)),
            "w2": _normal(k[6], (s.d_ff, s.d)),
            "attn_norm": _norm_scale(k[7], s.d),
            "mlp_norm": _norm_scale(k[8], s.d)}


def global_weights(s: Sizes, key) -> Dict[str, jax.Array]:
    k = jax.random.split(jax.random.fold_in(key, s.layers), 3)
    return {"embed": _normal(k[0], (s.vocab, s.d)),
            "lm_head": _normal(k[1], (s.d, s.vocab)),
            "final_norm": _norm_scale(k[2], s.d)}


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 32 bits)."""
    return jax.random.PRNGKey(
        int(np.random.SeedSequence(seed).generate_state(1)[0]))


@functools.partial(jax.jit, static_argnums=0)
def program_params(s: Sizes, key):
    """Every weight, in the program's stacked layout, in one call on the
    device: layer ``l`` of each stack is :func:`layer_weights` of ``l``."""
    st = jax.lax.map(lambda l: layer_weights(s, key, l),
                     jnp.arange(s.layers))
    g = global_weights(s, key)
    block = {"attn": {"wq": st["wq"], "wk": st["wk"], "wv": st["wv"],
                      "wo": st["wo"]},
             "ffn": {"wg": st["wg"], "wi": st["wi"], "wo": st["w2"]},
             "mixer_norm": st["attn_norm"], "ffn_norm": st["mlp_norm"]}
    return {"embed": g["embed"], "lm_head": g["lm_head"],
            "final_norm": g["final_norm"],
            "decoder": {"prefix": [], "blocks": (block,), "suffix": []}}


# -- forward ---------------------------------------------------------------
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [T, heads, dh]; rotates the two halves of each head."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attend(s: Sizes, q, k, v):
    """Causal attention of one sequence: q [T, H, dh], k/v [T, Kv, dh]."""
    T = q.shape[0]
    g = s.heads // s.kv_heads
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("thd,uhd->htu", q, k) * s.head_dim ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((T, T), bool)), sc, -jnp.inf)
    return jnp.einsum("htu,uhd->thd", jax.nn.softmax(sc, -1), v)


@functools.partial(jax.jit, static_argnums=0)
def _layer(s: Sizes, w, x):
    """One layer over a batch of sequences x [n, T, d], float32."""
    with jax.default_matmul_precision("highest"):
        n, T, _ = x.shape
        pos = jnp.arange(T)
        h = _rms(x, w["attn_norm"], s.eps)
        q = (h @ w["wq"]).reshape(n, T, s.heads, s.head_dim)
        k = (h @ w["wk"]).reshape(n, T, s.kv_heads, s.head_dim)
        v = (h @ w["wv"]).reshape(n, T, s.kv_heads, s.head_dim)
        q = jax.vmap(_rope, (0, None, None))(q, pos, s.rope_theta)
        k = jax.vmap(_rope, (0, None, None))(k, pos, s.rope_theta)
        o = jax.lax.map(lambda a: _attend(s, *a), (q, k, v))
        x = x + o.reshape(n, T, -1) @ w["wo"]
        h = _rms(x, w["mlp_norm"], s.eps)
        return x + (jax.nn.silu(h @ w["wg"]) * (h @ w["wi"])) @ w["w2"]


@functools.partial(jax.jit, static_argnums=0)
def _head(s: Sizes, g, x, at):
    """Logits [n, K, V] at positions ``at`` [n, K] of x [n, T, d]."""
    with jax.default_matmul_precision("highest"):
        xs = jnp.take_along_axis(x, at[..., None], axis=1)
        return _rms(xs, g["final_norm"], s.eps) @ g["lm_head"]


def _quantize(w, axis, low: str):
    """``w`` rounded to ``low`` (``"int8"``: symmetric int8; ``"fp8"``:
    4 exponent and 3 mantissa bits, e4m3), scaled per channel (reduced
    over ``axis``), and dequantized back to float32.  Both round with
    explicit ops: a cast to a float8 type and back is one XLA may fold
    away."""
    amax = jnp.maximum(jnp.max(jnp.abs(w), axis, keepdims=True), 1e-12)
    if low == "int8":
        scale = amax / 127
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    scale = amax / 240                  # e4m3's largest under IEEE rules
    return jax.lax.reduce_precision(w / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


@functools.partial(jax.jit, static_argnums=(0, 3))
def _f32_layer_weights(s: Sizes, key, l, low):
    w = {k: v.astype(jnp.float32) for k, v in layer_weights(s, key, l).items()}
    if low:
        for k in ("wq", "wk", "wv", "wo", "wg", "wi", "w2"):
            w[k] = _quantize(w[k], 0, low)
    return w


@functools.partial(jax.jit, static_argnums=(0, 2))
def _f32_globals(s: Sizes, key, low):
    g = {k: v.astype(jnp.float32) for k, v in global_weights(s, key).items()}
    if low:
        g["embed"] = _quantize(g["embed"], 1, low)
        g["lm_head"] = _quantize(g["lm_head"], 0, low)
    return g


def logits_at(s: Sizes, key, tokens: np.ndarray, at: np.ndarray,
              low=None) -> np.ndarray:
    """Reference logits ``[n, K, V]`` (float32) of the sequences
    ``tokens [n, T]`` at positions ``at [n, K]``, computed layer by layer
    so only one layer's weights are on the device at a time.

    ``low`` (``"int8"`` or ``"fp8"``) is the control: every matrix, the
    embedding and the output head rounded to that type per output
    channel (per row for the embedding), the rest as above."""
    g = _f32_globals(s, key, low)
    x = jnp.take(g["embed"], jnp.asarray(tokens), axis=0)
    for l in range(s.layers):
        x = _layer(s, _f32_layer_weights(s, key, l, low), x)
    return np.asarray(_head(s, g, x, jnp.asarray(at)))
