"""Batch header parsing op: pad, tile, run the generated kernel."""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.dsl import Protocol
from . import kernel
from .ref import parse_ref

LANES = kernel.LANES


def parse_headers(
    protocol: Protocol,
    field_names: Sequence[str],
    words: jnp.ndarray,            # [B, W] uint32 packed headers
    *,
    use_pallas: bool = True,
    block_rows: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns uint32 [B, len(field_names)] parsed field values."""
    b, w = words.shape
    if not use_pallas:
        return parse_ref(protocol, field_names, words)
    w_pad = -(-w // LANES) * LANES
    b_block = min(block_rows, b) if b else 1
    b_pad = -(-b // b_block) * b_block
    padded = jnp.zeros((b_pad, w_pad), dtype=jnp.uint32).at[:b, :w].set(words)
    parse = kernel.make_parser(protocol, field_names, block_rows=b_block, interpret=interpret)
    return parse(padded)[:b]
