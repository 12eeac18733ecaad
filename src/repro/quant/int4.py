"""int4 group quantization (WebLLM serves q4f16-quantized models).

Weights are quantized along the contraction dim (axis -2) in groups:
two int4 values pack into one int8 (low nibble = even row), scales are
bf16 per (group, column).  ``QTensor`` is a registered pytree node, so
quantized trees flow through jit / scan / shard_map transparently; the
dequant happens inside each consumer (scan body), keeping HBM residency
at 4 bits + scales.

Group size adapts so that group boundaries never straddle a 16-way
'model'-axis shard of the contraction dim.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.pdef import ParamDef, is_pdef, tree_map_defs

MODEL_AXIS_SIZE = 16          # production model-parallel degree
DEFAULT_GROUP = 64
MIN_K = 128                   # don't quantize tiny contractions

_SHARDED_K_AXES = {"d_ff", "heads_flat", "kv_flat", "d_inner", "vocab"}


@jax.tree_util.register_pytree_node_class
class QTensor:
    """Packed int4 weight: data int8 [..., K/2, N], scales bf16 [..., K/G, N]."""

    def __init__(self, data, scales, group: int):
        self.data = data
        self.scales = scales
        self.group = group

    @property
    def shape(self):
        s = list(self.data.shape)
        s[-2] *= 2
        return tuple(s)

    @property
    def dtype(self):
        return jnp.bfloat16

    def tree_flatten(self):
        return (self.data, self.scales), self.group

    @classmethod
    def tree_unflatten(cls, group, children):
        return cls(children[0], children[1], group)

    def dequant(self) -> jax.Array:
        d = self.data
        low = jnp.right_shift(jnp.left_shift(d, 4), 4)      # sign-extended
        high = jnp.right_shift(d, 4)
        q = jnp.stack([low, high], axis=-2)                 # [..., K/2, 2, N]
        new_shape = self.shape
        q = q.reshape(new_shape).astype(jnp.bfloat16)
        K = new_shape[-2]
        G = self.group
        qg = q.reshape(*new_shape[:-2], K // G, G, new_shape[-1])
        w = qg * self.scales[..., :, None, :].astype(jnp.bfloat16)
        return w.reshape(new_shape)

    def __repr__(self):
        return f"QTensor(shape={self.shape}, group={self.group})"


def is_qtensor(x) -> bool:
    return isinstance(x, QTensor)


def choose_group(K: int, k_sharded: bool) -> Optional[int]:
    if K < MIN_K or K % 2:
        return None
    g = DEFAULT_GROUP
    need = MODEL_AXIS_SIZE if k_sharded else 1
    while g >= 4:
        if K % (g * need) == 0:
            return g
        g //= 2
    return None


def should_quantize(d: ParamDef) -> Optional[int]:
    """Returns group size or None."""
    if d.init != "normal" or d.dtype != jnp.bfloat16 or len(d.shape) < 2:
        return None
    axes = d.axes or (None,) * len(d.shape)
    if "vocab" in axes:           # embed / lm_head stay bf16
        return None
    k_ax = axes[-2]
    k_sharded = k_ax in _SHARDED_K_AXES
    return choose_group(d.shape[-2], k_sharded)


def quantize_array(w: jax.Array, group: int) -> QTensor:
    """Symmetric per-(group, column) int4 quantization."""
    shape = w.shape
    K, N = shape[-2], shape[-1]
    wf = w.astype(jnp.float32).reshape(*shape[:-2], K // group, group, N)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)     # [..., K/G, 1, N]
    scale = jnp.maximum(amax / 7.0, 1e-8)
    q = jnp.clip(jnp.round(wf / scale), -8, 7).astype(jnp.int8)
    q = q.reshape(*shape[:-2], K, N)
    even = q[..., 0::2, :]
    odd = q[..., 1::2, :]
    packed = jnp.bitwise_or(
        jnp.bitwise_and(even, jnp.int8(0x0F)),
        jnp.left_shift(odd, 4)).astype(jnp.int8)
    scales = scale[..., 0, :].astype(jnp.bfloat16)          # [..., K/G, N]
    return QTensor(packed, scales, group)


def _q_shapes(d: ParamDef, group: int):
    data_shape = d.shape[:-2] + (d.shape[-2] // 2, d.shape[-1])
    scale_shape = d.shape[:-2] + (d.shape[-2] // group, d.shape[-1])
    return data_shape, scale_shape


def quantize_tree(params, defs):
    """Quantize materialized params per the defs tree."""
    flat_p, td = jax.tree.flatten(params)
    flat_d = jax.tree.leaves(defs, is_leaf=is_pdef)
    out = []
    for p, d in zip(flat_p, flat_d):
        g = should_quantize(d)
        out.append(quantize_array(p, g) if g else p)
    return jax.tree.unflatten(td, out)


def abstract_qtree(defs):
    """ShapeDtypeStruct tree with QTensor nodes (for AOT lowering)."""
    def one(_, d: ParamDef):
        g = should_quantize(d)
        if not g:
            return jax.ShapeDtypeStruct(d.shape, d.dtype)
        ds, ss = _q_shapes(d, g)
        return QTensor(jax.ShapeDtypeStruct(ds, jnp.int8),
                       jax.ShapeDtypeStruct(ss, jnp.bfloat16), g)
    return tree_map_defs(one, defs)


def qtree_pspecs(defs, mesh, rules: Optional[dict] = None):
    """PartitionSpec tree matching abstract_qtree structure."""
    from repro.models import pdef as pdef_mod
    rules = dict(pdef_mod.DEFAULT_RULES, **(rules or {}))
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def one(_, d: ParamDef):
        g = should_quantize(d)
        if not g:
            return pdef_mod.spec_for(d, rules, sizes)
        ds, ss = _q_shapes(d, g)
        import dataclasses
        d_data = dataclasses.replace(d, shape=ds, dtype=jnp.int8)
        d_scale = dataclasses.replace(d, shape=ss)
        return QTensor(pdef_mod.spec_for(d_data, rules, sizes),
                       pdef_mod.spec_for(d_scale, rules, sizes), g)
    return tree_map_defs(one, defs)


def dequant_tree(p):
    return jax.tree.map(lambda x: x.dequant() if is_qtensor(x) else x,
                        p, is_leaf=is_qtensor)


def qdot(x: jax.Array, w: Any) -> jax.Array:
    """Matmul with a maybe-quantized RHS: plain ``x @ w`` for ordinary
    arrays, W4A16 for :class:`QTensor` weights.

    On TPU every 2-D quantized weight feeds the Pallas ``w4a16_gemm``
    kernel directly (the weight stays 4-bit in HBM; dequant is fused
    into the K loop; the kernel pads the rows itself).  Stacked
    (scanned) weights, and every weight on hosts without a TPU, take
    ``x @ w.dequant()``.  Consumers (projections, MLP, paged runner)
    call this instead of ``@`` so a quantized tree serves unchanged.
    """
    if not is_qtensor(w):
        return x @ w
    if jax.default_backend() == "tpu" and w.data.ndim == 2:
        from repro.kernels.ops import w4a16_gemm
        K, N = w.shape
        lead = x.shape[:-1]
        y = w4a16_gemm(x.reshape(-1, K).astype(jnp.bfloat16),
                       w.data, w.scales, group=w.group,
                       block_n=128 if N % 128 == 0 else N)
        return y.reshape(*lead, N).astype(x.dtype)
    return x @ w.dequant()
