"""Generated header-parser Pallas kernel — §III-B.1 on TPU.

SPAC's parser is an HLS template instantiated with compile-time traits; here
``make_parser`` *generates* a Pallas kernel with the protocol's bit offsets
baked into the closure (the `packet.hpp` role).  Field accesses lower to
hard-wired shift/mask ops on 32-bit words; fields that straddle word
boundaries emit one extra shift-or (the "minimal state retention" analogue).
Batches of packed headers are parsed at VPU line rate: [B, W] uint32 words →
[B, F] uint32 field values.

Tiling: rows (packets) stream through in ``block_rows`` blocks; the word dim
is zero-padded to the 128-lane boundary inside ``ops.parse_headers``.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.dsl import Protocol

WORD_BITS = 32
LANES = 128


def bake_slices(protocol: Protocol, field_names: Sequence[str]):
    """Compile-time lowering: field -> ((word, lo, width, dst_shift), ...)."""
    plan = protocol.compile(WORD_BITS)
    baked = []
    for name in field_names:
        pieces = []
        for s in plan.slices_for(name):
            take = s.hi - s.lo + 1
            if s.dst_shift >= WORD_BITS:
                continue  # truncated to low 32 bits (lookup keys are <=32b)
            pieces.append((s.word, s.lo, take, s.dst_shift))
        baked.append(tuple(pieces))
    return tuple(baked)


def make_parser(
    protocol: Protocol,
    field_names: Sequence[str],
    *,
    block_rows: int = 256,
    interpret: bool = False,
) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Generate the specialised parser kernel for (protocol, fields)."""
    baked = bake_slices(protocol, field_names)
    n_fields = len(baked)
    f_pad = -(-n_fields // LANES) * LANES

    def _kernel(w_ref, o_ref):
        words = w_ref[...]                                 # [br, Wpad] uint32
        cols = []
        for pieces in baked:                               # unrolled at trace time
            v = jnp.zeros(words.shape[:1], dtype=jnp.uint32)
            for word, lo, take, dst_shift in pieces:
                piece = (words[:, word] >> jnp.uint32(lo)) & jnp.uint32((1 << take) - 1)
                v = v | (piece << jnp.uint32(dst_shift))
            cols.append(v)
        for _ in range(f_pad - n_fields):
            cols.append(jnp.zeros(words.shape[:1], dtype=jnp.uint32))
        o_ref[...] = jnp.stack(cols, axis=1)

    @functools.partial(jax.jit, static_argnames=())
    def parse(words_padded: jnp.ndarray) -> jnp.ndarray:
        b, w_pad = words_padded.shape
        br = min(block_rows, b)
        assert b % br == 0, f"batch {b} not divisible by block_rows {br}"
        out = pl.pallas_call(
            _kernel,
            grid=(b // br,),
            in_specs=[pl.BlockSpec((br, w_pad), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((br, f_pad), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((b, f_pad), jnp.uint32),
            interpret=interpret,
        )(words_padded)
        return out[:, :n_fields]

    return parse
