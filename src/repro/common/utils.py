"""Small shared utilities: bit manipulation, tree helpers."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Bit-field packing helpers (uint32 words).
# ---------------------------------------------------------------------------

def next_pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1). Shared by the serving engine's
    prefill bucketing and the fabric's window-count bucketing — both bound
    compiled-shape counts to O(log) distinct sizes."""
    return 1 << max(n - 1, 1).bit_length() if n > 1 else 1


def get_bits(word: jnp.ndarray, lo: int, width: int) -> jnp.ndarray:
    """Extract ``width`` bits starting at bit ``lo`` from uint32 word(s)."""
    mask = jnp.uint32((1 << width) - 1)
    return (word >> jnp.uint32(lo)) & mask


def set_bits(word: jnp.ndarray, lo: int, width: int, value: jnp.ndarray) -> jnp.ndarray:
    """Return ``word`` with ``width`` bits at ``lo`` replaced by ``value``."""
    mask = jnp.uint32((1 << width) - 1)
    value = jnp.asarray(value).astype(jnp.uint32) & mask
    cleared = word & ~(mask << jnp.uint32(lo))
    return cleared | (value << jnp.uint32(lo))


def bitcast_bf16_to_u16(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(x.astype(jnp.bfloat16), jnp.uint16)


def bitcast_u16_to_bf16(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(x.astype(jnp.uint16), jnp.bfloat16)


def u16_to_bytes(x: jnp.ndarray) -> jnp.ndarray:
    """uint16[N] -> uint8[2N] little-endian."""
    lo = (x & jnp.uint16(0xFF)).astype(jnp.uint8)
    hi = (x >> jnp.uint16(8)).astype(jnp.uint8)
    return jnp.stack([lo, hi], axis=-1).reshape(x.shape[:-1] + (x.shape[-1] * 2,))


def bytes_to_u16(b: jnp.ndarray) -> jnp.ndarray:
    """uint8[2N] -> uint16[N] little-endian."""
    pairs = b.reshape(b.shape[:-1] + (b.shape[-1] // 2, 2)).astype(jnp.uint16)
    return pairs[..., 0] | (pairs[..., 1] << jnp.uint16(8))


def u32_to_bytes(u: jnp.ndarray) -> jnp.ndarray:
    """uint32[..., N] -> uint8[..., 4N] little-endian."""
    u = u.astype(jnp.uint32)
    out = [((u >> jnp.uint32(s)) & jnp.uint32(0xFF)).astype(jnp.uint8)
           for s in (0, 8, 16, 24)]
    return jnp.stack(out, axis=-1).reshape(u.shape[:-1] + (u.shape[-1] * 4,))


def bytes_to_u32(b: jnp.ndarray) -> jnp.ndarray:
    """uint8[..., 4N] -> uint32[..., N] little-endian."""
    quads = b.reshape(b.shape[:-1] + (b.shape[-1] // 4, 4)).astype(jnp.uint32)
    return (quads[..., 0] | (quads[..., 1] << 8) | (quads[..., 2] << 16)
            | (quads[..., 3] << 24))


def f32_to_bytes(x: jnp.ndarray) -> jnp.ndarray:
    return u32_to_bytes(
        jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32))


def bytes_to_f32(b: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(bytes_to_u32(b), jnp.float32)


# ---------------------------------------------------------------------------
# Tree / shape helpers.
# ---------------------------------------------------------------------------

def tree_bytes(tree: Any) -> int:
    return sum(np.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "shape"))


def tree_count(tree: Any) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "shape"))


def assert_finite(tree: Any, name: str = "tree") -> None:
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            if not bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32)))):
                raise AssertionError(f"non-finite values in {name}{jax.tree_util.keystr(path)}")


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b
