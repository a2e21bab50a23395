"""Layout ``words4`` (multi-spin coding): one lattice, each colour plane
an (n, m/16) uint32 array of 4-bit cells, cell 8w + j of a row in bits
4j..4j+3 of word w, holding 0 for spin -1 and 1 for spin +1."""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as ref

LATTICES = 1

_SHIFTS = np.arange(8, dtype=np.uint32) * np.uint32(4)


def hot_start(key, n, m):
    """A colour plane of a hot start, each spin +1 or -1 with
    probability 1/2."""
    bits = jax.random.bits(key, (n, m // 16), jnp.uint32)
    return bits & jnp.uint32(0x11111111)


def _unpack(words):
    v = (words[..., None] >> _SHIFTS) & jnp.uint32(0xF)
    spins = 2 * v.astype(jnp.int32) - 1
    return spins.reshape(words.shape[0], -1).astype(jnp.int8)


@jax.jit
def plane(a, r):
    """Lattice ``r`` of ``a`` as an int8 plane of spins 2v - 1; a cell
    holding anything but 0 or 1 comes out as neither -1 nor +1."""
    n, w = a.shape
    return ref.by_blocks(lambda rows: _unpack(jnp.take(a, rows, axis=0)),
                         n, 8 * w).reshape(n, 8 * w)


@jax.jit
def put(a, r, plane):
    """``a`` with lattice ``r`` replaced by the int8 +-1 ``plane``."""
    n, width = plane.shape

    def block(rows):
        v = (jnp.take(plane, rows, axis=0).astype(jnp.int32) + 1) // 2
        v = v.astype(jnp.uint32).reshape(rows.shape[0], width // 8, 8)
        return jnp.sum(v << _SHIFTS, axis=-1, dtype=jnp.uint32)

    return ref.by_blocks(block, n, width).reshape(n, width // 8)


@jax.jit
def count_differ(ref_plane, a, r):
    """Cells of lattice ``r`` of ``a`` that differ from ``ref_plane``."""
    n, width = ref_plane.shape

    def block(rows):
        return jnp.sum(jnp.take(ref_plane, rows, axis=0)
                       != _unpack(jnp.take(a, rows, axis=0)),
                       dtype=jnp.int32)

    return jnp.sum(ref.by_blocks(block, n, width), dtype=jnp.int32)
