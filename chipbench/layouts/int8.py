"""Layout ``int8`` (the basic stencil engines): one lattice, each colour
plane an (n, m/2) int8 array of spins +-1, the reference's own plane."""
import jax
import jax.numpy as jnp

from chipbench import reference as ref

LATTICES = 1


def hot_start(key, n, m):
    """A colour plane of a hot start, each spin +1 or -1 with
    probability 1/2."""
    bits = jax.random.bits(key, (n, m // 2), jnp.uint8)
    return (2 * (bits & 1) - 1).astype(jnp.int8)


def plane(a, r):
    """Lattice ``r`` of the array ``a`` as an int8 +-1 plane."""
    return a


def put(a, r, plane):
    """``a`` with lattice ``r`` replaced by the int8 +-1 ``plane``."""
    return plane


@jax.jit
def count_differ(ref_plane, a, r):
    """Cells of lattice ``r`` of ``a`` that differ from ``ref_plane``."""
    n, width = ref_plane.shape

    def block(rows):
        return jnp.sum(jnp.take(ref_plane, rows, axis=0)
                       != jnp.take(a, rows, axis=0), dtype=jnp.int32)

    return jnp.sum(ref.by_blocks(block, n, width), dtype=jnp.int32)
