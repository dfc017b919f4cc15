"""The matrix product of the plain references, in a stated precision.

``float32`` is the reference proper: float32 operands, and on a TPU
``Precision.HIGHEST`` so that the product is not quietly done in bfloat16.
The lower precisions are what the *controls* compute in: the reference put in
the program's place one step below what the configuration states
(``bfloat16`` for float32, ``int8`` for bfloat16), which the comparison has
to reject.

``int8`` is the usual W8A8 scheme: each operand is rounded to 255 levels
with one scale for each row of the left operand and for each column of the
right one, and the products are summed exactly. The backward products (for a
training control) are rounded the same way, straight through the rounding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _q8(x, axis):
    """Round ``x`` to the int8 grid with one absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm_f32(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _mm_q8(a, b):
    return _mm_f32(_q8(a, -1), _q8(b, -2))


@jax.custom_vjp
def _mm_int8(a, b):
    return _mm_q8(a, b)


def _mm_int8_fwd(a, b):
    return _mm_q8(a, b), (a, b)


def _mm_int8_bwd(res, g):
    a, b = res
    da = _mm_q8(g, jnp.swapaxes(b, -1, -2))
    # Sum the left operand's leading axes away: b is [k, n], a is [..., k].
    a2 = a.reshape(-1, a.shape[-1])
    g2 = g.reshape(-1, g.shape[-1])
    db = _mm_q8(a2.T, g2) if b.ndim == 2 else \
        _mm_q8(jnp.swapaxes(a, -1, -2), g)
    return da.reshape(a.shape), db.reshape(b.shape)


_mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)


def matmul(precision: str):
    """``mm(a, b)`` for ``a [..., k] @ b [k, n]`` (or batched ``b``)."""
    if precision == "float32":
        return _mm_f32
    if precision == "bfloat16":
        return lambda a, b: jnp.matmul(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32)
    if precision == "int8":
        return _mm_int8
    raise ValueError(f"unknown precision {precision!r}: float32, bfloat16 "
                     f"or int8")


def below(stated: str) -> str:
    """The nearest precision below the one a configuration states."""
    return {"float32": "bfloat16", "bfloat16": "int8"}[stated]
