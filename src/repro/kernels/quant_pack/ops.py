"""Public ops for payload compression, with a jax-native fallback.

``compress``/``decompress`` round-trip arbitrary-shaped tensors by flattening
to [R, 128k].  The default is the pure-jnp reference (identical math), so
the HLO compiles on any backend; ``use_pallas=True`` takes the Pallas kernel,
the TPU deployment path.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

from . import kernel, ref

GROUP = kernel.GROUP


def _to_2d(x: jnp.ndarray) -> Tuple[jnp.ndarray, tuple]:
    shape = x.shape
    flat = int(np.prod(shape))
    pad = (-flat) % GROUP
    v = jnp.pad(x.reshape(-1), (0, pad))
    return v.reshape(-1, GROUP), (shape, flat)


def compress(x: jnp.ndarray, *, use_pallas: bool = False):
    """tensor -> (q int8 [R,128], scales f32 [R,1], meta) — the wire format."""
    v, meta = _to_2d(x)
    if use_pallas:
        q, s = kernel.quantize(v)
    else:
        q, s = ref.quantize_ref(v)
    return q, s, meta


def decompress(q: jnp.ndarray, s: jnp.ndarray, meta, *, dtype=jnp.float32,
               use_pallas: bool = False) -> jnp.ndarray:
    shape, flat = meta
    x = kernel.dequantize(q, s, out_dtype=dtype) if use_pallas else ref.dequantize_ref(q, s, dtype)
    return x.reshape(-1)[:flat].reshape(shape)


def compression_ratio(x: jnp.ndarray) -> float:
    """Wire-bytes ratio vs the uncompressed dtype (the 'header compression' win)."""
    in_bytes = x.size * x.dtype.itemsize
    out_bytes = x.size * 1 + (x.size // GROUP) * 4
    return in_bytes / out_bytes
