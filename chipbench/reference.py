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

This module holds what every random stream shares: Philox4x32-10, the
neighbour geometry, row blocking, the sweep and offset loop and the
exact observables.  A stream (``chipbench/streams/<stream>.py``) says
which Philox numbers a cell draws and how it accepts a flip, in its
``flips(t, nn, rows, beta, k0, k1, offset, precision)``.  A replay
advances several lattices at once (a state can hold many, as bitplane
words hold 32), and the stream draws each row block once for all of
them.

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
    """Row-block height whose temporaries of 4-byte cells stay near
    64 MiB each."""
    rows = max(2, (1 << 24) // max(width, 1))
    while n % rows:
        rows //= 2
    return max(1, min(rows, n))


def by_blocks(fn, n: int, width: int):
    """``fn(rows)`` over the row blocks of an n-row plane, stacked;
    ``width`` is the cells a row of the block holds."""
    per = _block_rows(n, width)
    return jax.lax.map(
        lambda b: fn(b * per + jnp.arange(per, dtype=jnp.int32)),
        jnp.arange(n // per, dtype=jnp.int32))


@functools.partial(jax.jit,
                   static_argnames=("is_black", "stream", "precision"))
def half_sweep(targets, ops, beta, k0, k1, offset, *, is_black: bool,
               stream, precision: str = "float32"):
    """One colour half-sweep of each lattice's ``targets`` plane (int8
    +-1) against its ``ops`` plane; ``stream`` is the module that draws
    a row block once for all the lattices and says which cells flip."""
    n, width = targets[0].shape

    def block(rows):
        t = jnp.stack([jnp.take(p, rows, axis=0) for p in targets])
        nn = jnp.stack([neighbours(p, rows, is_black) for p in ops])
        flip = stream.flips(t, nn, rows, beta, k0, k1, offset, precision)
        return tuple(jnp.where(flip, -t, t))

    return tuple(b.reshape(n, width)
                 for b in by_blocks(block, n, len(targets) * width))


def sweeps(blacks, whites, *, temperature: float, seed: int, step0: int,
           n_sweeps: int, stream, precision: str = "float32",
           observe_every: int = 0):
    """``n_sweeps`` sweeps of the lattices whose colour planes are
    ``blacks[i]``, ``whites[i]``, from the cumulative sweep count
    ``step0``.

    Returns ``(blacks, whites, samples)``; with ``observe_every`` = j > 0,
    every j-th sweep is followed by a sample: for each lattice ``(M,
    B)``, the sum of the spins and the sum over bonds of s_i s_j, exact
    python ints (floats accumulated in bfloat16 for the control)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    beta = jnp.float32(inv_temp(temperature))
    k0, k1 = keys(seed)
    blacks, whites = tuple(blacks), tuple(whites)
    samples = []
    for s in range(n_sweeps):
        for colour in (0, 1):
            off = np.uint32((2 * (step0 + s) + colour) % 2 ** 32)
            if colour == 0:
                blacks = half_sweep(blacks, whites, beta, k0, k1, off,
                                    is_black=True, stream=stream,
                                    precision=precision)
            else:
                whites = half_sweep(whites, blacks, beta, k0, k1, off,
                                    is_black=False, stream=stream,
                                    precision=precision)
        if observe_every and (s + 1) % observe_every == 0:
            samples.append([observables(b, w, precision)
                            for b, w in zip(blacks, whites)])
    return blacks, whites, samples


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

    spins, bonds = by_blocks(block, n, width)
    return spins.reshape(n), bonds.reshape(n)


def observables(black, white, precision: str = "float32"):
    """(M, B) of one lattice: the sum of spins and the bond sum; exact
    integers, or in bfloat16 accumulated row by row for the control."""
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
