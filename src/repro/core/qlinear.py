"""Quantized linear layers: init, post-training quantization, apply.

A :class:`Linear` is a registered pytree whose children are the weight
(dense array *or* ``Q8_0Tensor``/``Q3KTensor`` after quantization) and
optional bias; the tensor *role* rides along as static aux data so
policies can be applied under ``jit``/``pjit`` without string leaves.
Weights are stored output-major ``(N, K)``, matching the kernel layout.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core.policy import OffloadPolicy
from repro.core.quant import Q3KTensor, Q4_0Tensor, Q8_0Tensor
from repro.kernels import ops

# ---------------------------------------------------------------------
# Matmul recorder: benchmarks install a callback here to enumerate every
# dot-product site (role, m, n, k) — the basis of the Table I
# reproduction.  ``None`` in production = zero overhead.
_RECORDER = None


def set_recorder(fn) -> None:
    global _RECORDER
    _RECORDER = fn


def record_matmul(name: str, role: str, m: int, n: int, k: int,
                  count: int = 1, act_act: bool = False) -> None:
    if _RECORDER is not None:
        _RECORDER(name=name, role=role, m=m, n=n, k=k, count=count,
                  act_act=act_act)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Linear:
    w: Any                      # (N, K) array | Q8_0Tensor | Q3KTensor
    b: Any = None               # (N,) array | None
    role: str = "proj_misc"     # static

    def tree_flatten(self):
        return (self.w, self.b), self.role

    @classmethod
    def tree_unflatten(cls, role, children):
        return cls(children[0], children[1], role)


def init_linear(key: jax.Array, in_dim: int, out_dim: int, *,
                role: str, bias: bool = False,
                dtype=jnp.bfloat16, scale: float | None = None) -> Linear:
    std = scale if scale is not None else in_dim ** -0.5
    w = (jax.random.normal(key, (out_dim, in_dim), jnp.float32)
         * std).astype(dtype)
    b = jnp.zeros((out_dim,), dtype) if bias else None
    return Linear(w=w, b=b, role=role)


_QTYPES = (Q8_0Tensor, Q4_0Tensor, Q3KTensor)


def apply_linear(p: Linear, x: jax.Array, *,
                 force: ops.Force = "auto") -> jax.Array:
    w = p.w
    if _RECORDER is not None:
        n_, k_ = (w.shape[-2], w.shape[-1])
        m_ = 1
        for d in x.shape[:-1]:
            m_ *= int(d)
        record_matmul("linear", p.role, m_, int(n_), int(k_))
    if isinstance(w, _QTYPES):
        y = ops.quantized_matmul(x, w, force=force)
    else:
        y = jax.lax.dot_general(
            x.astype(w.dtype), w,
            dimension_numbers=(((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)
    if p.b is not None:
        y = y + p.b.astype(y.dtype)
    return y


def quantize_linear(p: Linear, policy: OffloadPolicy) -> Linear:
    """Post-training quantization of one linear layer."""
    fmt = policy.format_for(p.role)
    w = p.w
    if isinstance(w, _QTYPES):
        return p
    if not fmt.startswith("q"):
        return Linear(quant.quantize(w, fmt), p.b, p.role)
    kw = {"scale_bits": policy.scale_bits} if fmt == "q3_k" else {}
    # Quantized axis is K (last); roles whose K doesn't divide the block
    # stay unquantized (GGML keeps such tensors in F16 as well).
    block = 256 if fmt == "q3_k" else 32
    if w.shape[-1] % block:
        return p
    return Linear(quant.quantize(w, fmt, **kw), p.b, p.role)


def quantize_params(params: Any, policy: OffloadPolicy) -> Any:
    """Walk a param pytree, quantizing every Linear per the policy.

    Generic over containers (dicts, lists, Conv, NamedTuples): Linears
    are treated as leaves of the traversal."""
    return jax.tree.map(
        lambda node: (quantize_linear(node, policy)
                      if isinstance(node, Linear) else node),
        params, is_leaf=lambda x: isinstance(x, Linear))


def is_packed(x) -> bool:
    """Whether ``x`` is a packed quantized weight (Q8_0, Q4_0, Q3_K)."""
    return isinstance(x, _QTYPES)


def param_bytes(params: Any) -> int:
    """Total parameter storage bytes (quantized tensors count packed)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params, is_leaf=is_packed):
        if is_packed(leaf):
            total += leaf.nbytes()
        elif hasattr(leaf, "dtype"):
            total += leaf.size * leaf.dtype.itemsize
    return total


def param_count(params: Any) -> int:
    """Logical parameter count (quantized tensors count logical size)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params, is_leaf=is_packed):
        if is_packed(leaf):
            total += int(jnp.prod(jnp.array(leaf.shape)))
        elif hasattr(leaf, "size"):
            total += leaf.size
    return total
