"""How the reference multiplies. ``Numerics("float32")`` is the plain
reference: float32 operands, ``Precision.HIGHEST`` (on a TPU a float32
matmul otherwise runs as one bfloat16 pass). A lower ``operand`` dtype is the
CONTROL: both operands of every matmul and convolution are rounded to that
dtype first (products still accumulate in float32), which is what computing
in that precision would do to the numbers — ``float8_e4m3fn`` is the nearest
step below the bfloat16 the configurations state."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


class Numerics:
    def __init__(self, operand: str = "float32"):
        self.operand = operand
        self._dt = None if operand == "float32" else jnp.dtype(operand)

    def _r(self, x):
        x = x.astype(jnp.float32)
        if self._dt is None:
            return x
        return x.astype(self._dt).astype(jnp.float32)

    def dense(self, x, kernel, bias=None):
        y = jnp.matmul(self._r(x), self._r(kernel), precision=HIGHEST)
        return y if bias is None else y + bias.astype(jnp.float32)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self._r(a), self._r(b), precision=HIGHEST)

    def conv(self, x, kernel, bias=None, *, stride=1, padding=1):
        """NHWC x HWIO, the same padding on both sides (or a list)."""
        pad = ([(padding, padding)] * 2 if isinstance(padding, int)
               else padding)
        y = lax.conv_general_dilated(
            self._r(x), self._r(kernel), (stride, stride), pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
        return y if bias is None else y + bias.astype(jnp.float32)


def group_norm(x, scale, bias, groups: int, eps: float, silu: bool = False):
    """torch GroupNorm over ``(N, ..., C)``: per sample and group, statistics
    pooled over every axis but the first and the channel's group, biased
    variance, float32."""
    x = x.astype(jnp.float32)
    n, c = x.shape[0], x.shape[-1]
    g = x.reshape(n, -1, groups, c // groups)
    mean = g.mean(axis=(1, 3), keepdims=True)
    var = ((g - mean) ** 2).mean(axis=(1, 3), keepdims=True)
    y = ((g - mean) * lax.rsqrt(var + eps)).reshape(x.shape)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y * jax.nn.sigmoid(y) if silu else y


def layer_norm(x, scale, bias, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return ((x - mean) * lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            + bias.astype(jnp.float32))


def silu(x):
    return x * jax.nn.sigmoid(x)


class Weights:
    """``{"a/b/kernel": array}`` with a moving prefix."""

    def __init__(self, flat: dict, prefix: str = ""):
        self.flat, self.prefix = flat, prefix

    def at(self, name: str) -> "Weights":
        return Weights(self.flat, f"{self.prefix}{name}/")

    def __call__(self, name: str):
        return self.flat[self.prefix + name]

    def has(self, name: str) -> bool:
        return (self.prefix + name) in self.flat
