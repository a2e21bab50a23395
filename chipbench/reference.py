"""Plain jax.numpy reference of one checkerboard Metropolis call.

The benchmark decides ``correct`` by replaying, with this module, a call
that the timed window made, and comparing spin for spin.  It imports
nothing of the program: the semantics are written down here from the
published scheme (arXiv:1906.06297, cuRAND-style counter-based Philox).

Lattice: an (n, m) lattice of spins +-1 with periodic edges, split into
two compact colour planes of shape (n, m/2); black[i, k] is the spin at
column 2k + i % 2, white[i, k] the one at column 2k + (i + 1) % 2.  A
sweep updates black, then white; half-sweep ``c`` of sweep ``s`` (both
counted from the start of the run) has the Philox offset 2s + c.

Two random streams, one per engine family:

* ``site`` (basic stencil): the cell at (i, k) of the target plane draws
  ``philox4x32_10(counter=(offset, 0, i * m/2 + k, 0), key)[0]``, turns it
  into u = float32(bits) * 2^-32 and flips when
  ``u < exp(-2 beta * nn * s)`` in float32, nn being the sum of its four
  neighbours;
* ``word`` (multi-spin coding, 8 cells to a word): the cells 8w..8w+7 of
  row i share word w' = i * m/16 + w; two Philox calls with counters
  (2 offset, 0, w', 0) and (2 offset + 1, 0, w', 0) give eight words, of
  which cell 8w + j takes number j.  A cell flips when that word is below
  a threshold: in 0/1 spins s and neighbour count c,
  p = exp(-2 beta (2s - 1)(2c - 4)) in float32, and the threshold is
  uint32(p * 2^32) for p < 1, else 2^32 - 1.

The key is (seed mod 2^32, seed >> 32).  ``precision="bfloat16"`` is the
control: the same call with the acceptance (and the observables) in
bfloat16, the nearest precision below the float32 the engines state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

PRECISIONS = ("float32", "bfloat16")

_M0 = 0xD2511F53
_M1 = 0xCD9E8D57
_W0 = 0x9E3779B9
_W1 = 0xBB67AE85
_ROUNDS = 10


def _u32(x):
    return jnp.asarray(x, jnp.uint32)


def _mul_hi_lo(a: int, b):
    """(high, low) 32-bit halves of the 64-bit product a * b."""
    b = _u32(b)
    a_lo, a_hi = _u32(a & 0xFFFF), _u32(a >> 16)
    b_lo, b_hi = b & 0xFFFF, b >> 16
    ll = a_lo * b_lo
    lh = a_lo * b_hi
    hl = a_hi * b_lo
    carry = ((ll >> 16) + (lh & 0xFFFF) + (hl & 0xFFFF)) >> 16
    hi = a_hi * b_hi + (lh >> 16) + (hl >> 16) + carry
    return hi, _u32(a) * b


def philox(c0, c1, c2, c3, k0, k1):
    """Philox4x32-10 (Salmon et al., SC'11) on broadcastable uint32s."""
    c0, c1, c2, c3 = _u32(c0), _u32(c1), _u32(c2), _u32(c3)
    k0, k1 = _u32(k0), _u32(k1)
    for r in range(_ROUNDS):
        hi0, lo0 = _mul_hi_lo(_M0, c0)
        hi1, lo1 = _mul_hi_lo(_M1, c2)
        c0, c1, c2, c3 = hi1 ^ c1 ^ k0, lo1, hi0 ^ c3 ^ k1, lo0
        k0 = k0 + _u32(_W0)
        k1 = k1 + _u32(_W1)
    return c0, c1, c2, c3


def keys(seed: int):
    return np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)


def inv_temp(temperature: float) -> np.float32:
    return np.float32(1.0 / float(temperature))


def _to_float(bits):
    """uint32 -> float32 rounded to nearest, through two exact halves."""
    hi = (bits >> 16).astype(jnp.int32).astype(jnp.float32)
    lo = (bits & 0xFFFF).astype(jnp.int32).astype(jnp.float32)
    return hi * jnp.float32(65536.0) + lo


def round_bf16(x):
    """float32 -> the nearest bfloat16 value (ties to even), kept as
    float32.  Done with integer ops: XLA on a TPU may run bfloat16
    arithmetic in float32 and drop the rounding (excess precision), which
    would leave the control exact."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def neighbours(op, rows, is_black: bool):
    """Sum of the four neighbours (in the opposite plane ``op``) of the
    target plane's cells on ``rows``, in op's own values (int8)."""
    n = op.shape[0]
    mid = jnp.take(op, rows, axis=0)
    up = jnp.take(op, (rows - 1) % n, axis=0)
    down = jnp.take(op, (rows + 1) % n, axis=0)
    odd = (rows % 2 == 1)[:, None]
    right = jnp.roll(mid, -1, axis=1)
    left = jnp.roll(mid, 1, axis=1)
    # black cells on odd rows sit right of op's cell k, on even rows left
    side = jnp.where(odd, right, left) if is_black \
        else jnp.where(odd, left, right)
    return up + down + mid + side


def _block_rows(n: int, width: int) -> int:
    """Row-block height whose draws stay near 64 MiB per lane."""
    rows = max(2, (1 << 24) // max(width, 1))
    while n % rows:
        rows //= 2
    return max(1, min(rows, n))


def _by_blocks(fn, n: int, width: int):
    """``fn(rows)`` over the row blocks of an n-row plane, stacked."""
    per = _block_rows(n, width)
    return jax.lax.map(
        lambda b: fn(b * per + jnp.arange(per, dtype=jnp.int32)),
        jnp.arange(n // per, dtype=jnp.int32))


def _flips_site(t, nn, rows, beta, k0, k1, offset, width, precision):
    cols = jnp.arange(t.shape[1], dtype=jnp.uint32)[None, :]
    idx = rows.astype(jnp.uint32)[:, None] * jnp.uint32(width) + cols
    zero = jnp.zeros_like(idx)
    bits = philox(offset, zero, idx, zero, k0, k1)[0]
    u = _to_float(bits) * jnp.float32(2.0 ** -32)
    arg = jnp.float32(-2.0) * beta * nn.astype(jnp.float32) \
        * t.astype(jnp.float32)
    if precision == "bfloat16":
        return round_bf16(u) < round_bf16(jnp.exp(round_bf16(arg)))
    return u < jnp.exp(arg)


def thresholds(beta, precision: str = "float32"):
    """The 10 uint32 thresholds of the word stream, index 5 s + c."""
    s = jnp.arange(2, dtype=jnp.float32)[:, None]
    c = jnp.arange(5, dtype=jnp.float32)[None, :]
    arg = jnp.float32(-2.0) * beta * (2.0 * s - 1.0) * (2.0 * c - 4.0)
    if precision == "bfloat16":
        p = round_bf16(jnp.exp(round_bf16(arg)))
    else:
        p = jnp.exp(arg)
    scaled = (p * jnp.float32(2.0 ** 32)).astype(jnp.uint32)
    return jnp.where(p < 1.0, scaled, jnp.uint32(0xFFFFFFFF)).reshape(10)


def _flips_word(t, nn, rows, thr, k0, k1, offset, width):
    words = t.shape[1] // 8
    w = jnp.arange(words, dtype=jnp.uint32)[None, :]
    idx = rows.astype(jnp.uint32)[:, None] * jnp.uint32(width // 8) + w
    zero = jnp.zeros_like(idx)
    off2 = _u32(offset) * jnp.uint32(2)
    lanes = philox(off2, zero, idx, zero, k0, k1) \
        + philox(off2 + jnp.uint32(1), zero, idx, zero, k0, k1)
    draws = jnp.stack(lanes, axis=-1).reshape(t.shape)
    s01 = (t.astype(jnp.int32) + 1) // 2
    c01 = (nn.astype(jnp.int32) + 4) // 2
    return draws < jnp.take(thr, 5 * s01 + c01)


@functools.partial(jax.jit,
                   static_argnames=("is_black", "stream", "precision"))
def half_sweep(target, op, beta, k0, k1, offset, *, is_black: bool,
               stream: str, precision: str = "float32"):
    """One colour half-sweep of ``target`` (int8 +-1) against ``op``."""
    n, width = target.shape
    thr = thresholds(beta, precision) if stream == "word" else None

    def block(rows):
        t = jnp.take(target, rows, axis=0)
        nn = neighbours(op, rows, is_black)
        if stream == "site":
            flip = _flips_site(t, nn, rows, beta, k0, k1, offset, width,
                               precision)
        else:
            flip = _flips_word(t, nn, rows, thr, k0, k1, offset, width)
        return jnp.where(flip, -t, t)

    return _by_blocks(block, n, width).reshape(n, width)


def sweeps(black, white, *, temperature: float, seed: int, step0: int,
           n_sweeps: int, stream: str, precision: str = "float32",
           observe_every: int = 0):
    """``n_sweeps`` sweeps from the cumulative sweep count ``step0``.

    Returns ``(black, white, samples)``; with ``observe_every`` = j > 0,
    every j-th sweep is followed by a sample ``(M, B)``: the sum of the
    spins and the sum over bonds of s_i s_j, exact python ints (floats
    accumulated in bfloat16 for the control)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    beta = jnp.float32(inv_temp(temperature))
    k0, k1 = keys(seed)
    samples = []
    for s in range(n_sweeps):
        for colour in (0, 1):
            off = np.uint32((2 * (step0 + s) + colour) % 2 ** 32)
            if colour == 0:
                black = half_sweep(black, white, beta, k0, k1, off,
                                   is_black=True, stream=stream,
                                   precision=precision)
            else:
                white = half_sweep(white, black, beta, k0, k1, off,
                                   is_black=False, stream=stream,
                                   precision=precision)
        if observe_every and (s + 1) % observe_every == 0:
            samples.append(observables(black, white, precision))
    return black, white, samples


@jax.jit
def _row_sums(black, white):
    """Per-row spin sums and black-cell bond sums (each bond joins one
    black and one white cell, so the black cells count every bond
    once)."""
    n, width = black.shape

    def block(rows):
        b = jnp.take(black, rows, axis=0)
        w = jnp.take(white, rows, axis=0)
        spins = (jnp.sum(b, axis=1, dtype=jnp.int32)
                 + jnp.sum(w, axis=1, dtype=jnp.int32))
        bonds = jnp.sum(b.astype(jnp.int32)
                        * neighbours(white, rows, True).astype(jnp.int32),
                        axis=1, dtype=jnp.int32)
        return spins, bonds

    spins, bonds = _by_blocks(block, n, width)
    return spins.reshape(n), bonds.reshape(n)


def observables(black, white, precision: str = "float32"):
    """(M, B): the sum of spins and the bond sum; exact integers, or in
    bfloat16 accumulated row by row for the control."""
    spins, bonds = (np.asarray(x, np.int64) for x in _row_sums(black,
                                                               white))
    if precision == "float32":
        return int(spins.sum()), int(bonds.sum())
    bf = ml_dtypes.bfloat16
    m = bf(0)
    b = bf(0)
    for rs, rb in zip(spins, bonds):
        m = bf(float(m) + float(bf(rs)))
        b = bf(float(b) + float(bf(rb)))
    return float(m), float(b)


# -- layouts -----------------------------------------------------------------

_SHIFTS = np.arange(8, dtype=np.uint32) * np.uint32(4)


def _unpack(words):
    v = (words[..., None] >> _SHIFTS) & jnp.uint32(0xF)
    spins = 2 * v.astype(jnp.int32) - 1
    return spins.reshape(words.shape[0], -1).astype(jnp.int8)


@jax.jit
def unpack_words(words):
    """(n, W) uint32 words of 4-bit cells (cell 8w + j in bits 4j..4j+3,
    value 0/1) -> (n, 8W) int8 spins 2v - 1; a cell holding anything but
    0 or 1 comes out as neither -1 nor +1."""
    n, w = words.shape
    return _by_blocks(lambda rows: _unpack(jnp.take(words, rows, axis=0)),
                      n, 8 * w).reshape(n, 8 * w)


@jax.jit
def pack_words(plane):
    """(n, 8W) int8 spins +-1 -> (n, W) uint32 words of 4-bit cells, the
    inverse of :func:`unpack_words`."""
    n, width = plane.shape

    def block(rows):
        v = (jnp.take(plane, rows, axis=0).astype(jnp.int32) + 1) // 2
        v = v.astype(jnp.uint32).reshape(rows.shape[0], width // 8, 8)
        return jnp.sum(v << _SHIFTS, axis=-1, dtype=jnp.uint32)

    return _by_blocks(block, n, width).reshape(n, width // 8)


@functools.partial(jax.jit, static_argnames=("layout",))
def count_differ(ref, got, layout: str = "int8"):
    """Cells of the int8 plane ``ref`` that differ from ``got``, which is
    held in ``layout``: ``int8`` (the same plane) or ``words4``."""
    n, width = ref.shape

    def block(rows):
        g = jnp.take(got, rows, axis=0)
        if layout == "words4":
            g = _unpack(g)
        return jnp.sum(jnp.take(ref, rows, axis=0) != g, dtype=jnp.int32)

    return jnp.sum(_by_blocks(block, n, width), dtype=jnp.int32)
