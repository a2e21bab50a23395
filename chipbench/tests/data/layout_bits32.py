"""Layout ``bits32`` (bitplane multi-spin coding, arXiv:1007.3726), a
test fixture: 32 lattices, each colour plane an (n, m/2) uint32 array
whose bit r holds lattice r's spin at that compact site, 0 for -1 and 1
for +1."""
import jax
import jax.numpy as jnp

LATTICES = 32


def hot_start(key, n, m):
    """A colour plane of 32 hot starts, each spin +1 or -1 with
    probability 1/2."""
    return jax.random.bits(key, (n, m // 2), jnp.uint32)


@jax.jit
def plane(a, r):
    """Lattice ``r`` of ``a`` as an int8 +-1 plane."""
    bit = (a >> jnp.asarray(r, jnp.uint32)) & jnp.uint32(1)
    return (2 * bit.astype(jnp.int8) - 1).astype(jnp.int8)


@jax.jit
def put(a, r, plane):
    """``a`` with lattice ``r`` replaced by the int8 +-1 ``plane``."""
    bit = jnp.uint32(1) << jnp.asarray(r, jnp.uint32)
    return (a & ~bit) | jnp.where(plane > 0, bit, jnp.uint32(0))


@jax.jit
def count_differ(ref_plane, a, r):
    """Cells of lattice ``r`` of ``a`` that differ from ``ref_plane``."""
    return jnp.sum(ref_plane != plane(a, r), dtype=jnp.int32)
