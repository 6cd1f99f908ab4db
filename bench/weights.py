"""Initial weights addressed by (seed, stream, element index).

Every value is a pure function of the run's seed, a stream number (one per
embedding field or dense leaf) and the element's flat index, computed with
integer mixing and one int->float conversion. So the benchmark can fill the
program's state in one jitted call, and the plain reference can rebuild the
same values for just the rows it needs, with nothing taken from the program.
The values are uniform with mean 0 and the standard deviation asked for.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

_M1 = 0x7FEB352D
_M2 = 0x846CA68B
_GOLD = 0x9E3779B9


def _u32(x):
    return jnp.asarray(x, jnp.uint32)


def _mix(x):
    """lowbias32 integer finalizer (bijective on uint32)."""
    x = x ^ (x >> 16)
    x = x * _u32(_M1)
    x = x ^ (x >> 15)
    x = x * _u32(_M2)
    return x ^ (x >> 16)


def seed_words(seed: int):
    """The seed as two uint32 words, to pass into a jitted function as an
    argument, so that its compiled program does not depend on the seed."""
    return jnp.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], jnp.uint32)


def _key(seed, stream):
    """``seed``: a Python int or ``seed_words(seed)``."""
    if isinstance(seed, int):
        seed = seed_words(seed)
    k = _mix(_u32(stream) * _u32(_GOLD) + _u32(0x7F4A7C15))
    k = _mix(k ^ seed[0])
    return _mix(k ^ seed[1])


def uniform(seed, stream, idx, std: float, dtype=jnp.float32):
    """Values at flat indices ``idx`` (uint32-able) of stream ``stream``:
    uniform on (-std*sqrt(3), std*sqrt(3)), so mean 0 and deviation std.
    ``stream`` may be an array broadcasting against ``idx``."""
    k = _key(seed, stream)
    x = _mix(_mix(_u32(idx) ^ k) + k)
    u = ((x >> 8).astype(jnp.float32) + 0.5) * (2.0 ** -23) - 1.0
    return (u * (std * math.sqrt(3.0))).astype(dtype)


def table_rows(seed, stream, rows, dim: int, std: float,
               dtype=jnp.float32):
    """``[len(rows), dim]`` rows of one embedding table (row-major flat
    index ``row * dim + j``)."""
    rows = jnp.asarray(rows, jnp.uint32)
    idx = rows[:, None] * _u32(dim) + jnp.arange(dim, dtype=jnp.uint32)[None, :]
    s = stream if jnp.ndim(stream) == 0 else jnp.asarray(stream)[:, None]
    return uniform(seed, s, idx, std, dtype)


def leaf(seed, stream: int, shape, std: float, dtype=jnp.float32):
    """A dense leaf of ``shape``; ``std == 0`` gives zeros."""
    n = math.prod(shape)
    if std == 0.0:
        return jnp.zeros(shape, dtype)
    return uniform(seed, stream, jnp.arange(n, dtype=jnp.uint32), std,
                   dtype).reshape(shape)
