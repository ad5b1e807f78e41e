"""Pallas TPU kernel: banded LSH keys over packed sketch words.

The banded prefilter (DESIGN.md §12) needs, per corpus row, one uint32 key
per *band* — a group of ``wpb`` contiguous packed words — such that two
rows collide on a band iff they agree on that whole word group. The key is
a seeded xorshift-multiply chain over the band's words:

    h = seed(t)
    for each word w in band t:  h = (h ^ w) * PRIME;  h ^= h >> 15

identical (uint32 wraparound) to the jnp oracle ``core.packed.band_hash``
and its numpy host twin — the kernel exists so index (re)builds at seal /
compact / distill time ride the same accelerator as the slab they hash.

Layout: the wrapper hands the words in as ``(wpb, nb_eff, B)`` — word
position within the band leading, bands on sublanes, rows on lanes — so
step ``j`` of the chain is one leading-index load ``src[j]`` of a
(nb_eff, TB) tile that advances every band of TB rows at once.

Grid: (rows / TB,), a trailing partial block allowed. Each program walks
the ``wpb`` steps in a loop and writes its (nb_eff, TB) key block.

VMEM per program (TB=128, W<=2048 words): 128·2048·4 B = 1 MiB in
(double-buffered 2 MiB); the key block is tiny.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.packed import _BAND_PRIME, _BAND_SEED

__all__ = ["band_hash_kernel"]


def _kernel(src_ref, out_ref):
    band = jax.lax.broadcasted_iota(jnp.uint32, out_ref.shape, 0)
    h0 = jnp.uint32(_BAND_SEED) * (band + jnp.uint32(1))

    def step(t, h):
        h = (h ^ src_ref[t]) * jnp.uint32(_BAND_PRIME)
        return h ^ (h >> jnp.uint32(15))

    out_ref[...] = jax.lax.fori_loop(0, src_ref.shape[0], step, h0)


def band_hash_kernel(
    src: jax.Array,
    *,
    block_rows: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """``src: (wpb, nb_eff, B)`` band-grouped words -> ``(nb_eff, B)``
    uint32 band keys; ``ops.band_hash`` does the grouping, the band-count
    clamp and the transposes."""
    wpb, nb_eff, bsz = src.shape
    return pl.pallas_call(
        _kernel,
        grid=(pl.cdiv(bsz, block_rows),),
        in_specs=[pl.BlockSpec((wpb, nb_eff, block_rows), lambda i: (0, 0, i))],
        out_specs=pl.BlockSpec((nb_eff, block_rows), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((nb_eff, bsz), jnp.uint32),
        name="band_hash",
        interpret=interpret,
    )(src)
