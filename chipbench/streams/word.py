"""Stream ``word`` (multi-spin coding, 8 cells to a word).

The cells 8w..8w+7 of row i share word w' = i * m/16 + w; two Philox
calls with counters (2 offset, 0, w', 0) and (2 offset + 1, 0, w', 0)
give eight words, of which cell 8w + j takes number j.  A cell flips when
that word is below a threshold: in 0/1 spins s and neighbour count c,
p = exp(-2 beta (2s - 1)(2c - 4)) in float32, and the threshold is
uint32(p * 2^32) for p < 1, else 2^32 - 1."""
import jax.numpy as jnp

from chipbench import reference as ref


def thresholds(beta, precision: str = "float32"):
    """The 10 uint32 thresholds, index 5 s + c."""
    s = jnp.arange(2, dtype=jnp.float32)[:, None]
    c = jnp.arange(5, dtype=jnp.float32)[None, :]
    arg = jnp.float32(-2.0) * beta * (2.0 * s - 1.0) * (2.0 * c - 4.0)
    if precision == "bfloat16":
        p = ref.round_bf16(jnp.exp(ref.round_bf16(arg)))
    else:
        p = jnp.exp(arg)
    scaled = (p * jnp.float32(2.0 ** 32)).astype(jnp.uint32)
    return jnp.where(p < 1.0, scaled, jnp.uint32(0xFFFFFFFF)).reshape(10)


def flips(t, nn, rows, beta, k0, k1, offset, precision):
    """Which cells of ``t`` (lattices, rows, m/2) flip; every lattice
    takes the same draws."""
    width = t.shape[-1]
    w = jnp.arange(width // 8, dtype=jnp.uint32)[None, :]
    idx = rows.astype(jnp.uint32)[:, None] * jnp.uint32(width // 8) + w
    zero = jnp.zeros_like(idx)
    off2 = jnp.asarray(offset, jnp.uint32) * jnp.uint32(2)
    lanes = ref.philox(off2, zero, idx, zero, k0, k1) \
        + ref.philox(off2 + jnp.uint32(1), zero, idx, zero, k0, k1)
    draws = jnp.stack(lanes, axis=-1).reshape(t.shape[-2:])
    s01 = (t.astype(jnp.int32) + 1) // 2
    c01 = (nn.astype(jnp.int32) + 4) // 2
    return draws < jnp.take(thresholds(beta, precision), 5 * s01 + c01)
