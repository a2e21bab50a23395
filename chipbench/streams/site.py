"""Stream ``site`` (the basic stencil engines): one Philox call per cell.

The cell at (i, k) of the target plane draws ``philox4x32_10(counter=
(offset, 0, i * m/2 + k, 0), key)[0]``, turns it into u = float32(bits)
* 2^-32 and flips when ``u < exp(-2 beta * nn * s)`` in float32, nn being
the sum of its four neighbours."""
import jax.numpy as jnp

from chipbench import reference as ref


def _to_float(bits):
    """uint32 -> float32 rounded to nearest, through two exact halves."""
    hi = (bits >> 16).astype(jnp.int32).astype(jnp.float32)
    lo = (bits & 0xFFFF).astype(jnp.int32).astype(jnp.float32)
    return hi * jnp.float32(65536.0) + lo


def flips(t, nn, rows, beta, k0, k1, offset, precision):
    """Which cells of ``t`` (lattices, rows, m/2) flip; every lattice
    takes the same draws."""
    width = t.shape[-1]
    cols = jnp.arange(width, dtype=jnp.uint32)[None, :]
    idx = rows.astype(jnp.uint32)[:, None] * jnp.uint32(width) + cols
    zero = jnp.zeros_like(idx)
    bits = ref.philox(offset, zero, idx, zero, k0, k1)[0]
    u = _to_float(bits) * jnp.float32(2.0 ** -32)
    arg = jnp.float32(-2.0) * beta * nn.astype(jnp.float32) \
        * t.astype(jnp.float32)
    if precision == "bfloat16":
        return ref.round_bf16(u) < ref.round_bf16(jnp.exp(
            ref.round_bf16(arg)))
    return u < jnp.exp(arg)
