"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lattice as lat
from repro.core import multispin as ms
from repro.core import tensorcore as tc
from repro.kernels.multispin.multispin import multispin_update
from repro.kernels.multispin.ops import run_sweeps_multispin
from repro.kernels.multispin.ref import multispin_update_ref
from repro.kernels.stencil.ops import run_sweeps_stencil
from repro.kernels.stencil.ref import stencil_update_ref
from repro.kernels.stencil.stencil import stencil_update
from repro.kernels.tensorcore.ref import tensorcore_update_ref
from repro.kernels.tensorcore.tensorcore import tensorcore_update

SHAPES = [(16, 32), (64, 64), (32, 128), (128, 256)]


@pytest.mark.parametrize("n,m", SHAPES)
@pytest.mark.parametrize("is_black", [True, False])
def test_stencil_kernel_philox(n, m, is_black):
    full = lat.init_lattice(jax.random.PRNGKey(0), n, m)
    b, w = lat.split_checkerboard(full)
    t, op = (b, w) if is_black else (w, b)
    beta = jnp.float32(1 / 2.2)
    out_k = stencil_update(t, op, beta, is_black=is_black, seed=9, offset=5,
                           block_rows=8, interpret=True)
    out_r = stencil_update_ref(t, op, beta, is_black=is_black, seed=9,
                               offset=5)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


@pytest.mark.parametrize("n,m", SHAPES[:2])
@pytest.mark.parametrize("dtype", [jnp.int8, jnp.int32])
def test_stencil_kernel_uniforms_dtypes(n, m, dtype):
    full = lat.init_lattice(jax.random.PRNGKey(1), n, m).astype(dtype)
    b, w = lat.split_checkerboard(full)
    u = jax.random.uniform(jax.random.PRNGKey(2), b.shape)
    beta = jnp.float32(0.7)
    out_k = stencil_update(b, w, beta, is_black=True, uniforms=u,
                           block_rows=8, interpret=True)
    out_r = stencil_update_ref(b, w, beta, is_black=True, uniforms=u)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
    assert out_k.dtype == dtype


@pytest.mark.parametrize("n,m", SHAPES)
@pytest.mark.parametrize("is_black", [True, False])
@pytest.mark.parametrize("seed", [11, (0xABCD << 32) | 11])  # 64-bit key
def test_multispin_kernel(n, m, is_black, seed):
    full = lat.init_lattice(jax.random.PRNGKey(3), n, m)
    bw, ww = ms.pack_lattice(*lat.split_checkerboard(full))
    t, op = (bw, ww) if is_black else (ww, bw)
    beta = jnp.float32(1 / 2.3)
    out_k = multispin_update(t, op, beta, is_black=is_black, seed=seed,
                             offset=3, block_rows=8, interpret=True)
    out_r = multispin_update_ref(t, op, beta, is_black=is_black, seed=seed,
                                 offset=3)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


@pytest.mark.parametrize("n,block", [(32, 8), (64, 16), (64, 8), (128, 32)])
@pytest.mark.parametrize("color", ["black", "white"])
def test_tensorcore_kernel(n, block, color):
    full = lat.init_lattice(jax.random.PRNGKey(4), n, n)
    planes = {k: v.astype(jnp.bfloat16)
              for k, v in tc.decompose(full).items()}
    beta = jnp.float32(1 / 2.27)
    out_k = tensorcore_update(planes, color, beta, seed=21, offset=7,
                              block=block, interpret=True)
    out_r = tensorcore_update_ref(planes, color, beta, seed=21, offset=7,
                                  block=block)
    for pk in out_k:
        np.testing.assert_array_equal(
            np.asarray(out_k[pk], np.float32),
            np.asarray(out_r[pk], np.float32), err_msg=f"{pk}")


def test_multisweep_wrappers_match_core():
    """ops.py sweep loops == core engine sweep loops, multi-iteration."""
    full = lat.init_lattice(jax.random.PRNGKey(5), 32, 64)
    b, w = lat.split_checkerboard(full)
    bw, ww = ms.pack_lattice(b, w)  # before the donating philox call
    beta = jnp.float32(1 / 2.0)
    bk, wk = run_sweeps_stencil(b.copy(), w.copy(), beta, 5, seed=2,
                                block_rows=8, interpret=True)  # donates
    from repro.core.metropolis import run_sweeps_philox
    br, wr = run_sweeps_philox(b, w, beta, 5, seed=2)  # donates b, w
    np.testing.assert_array_equal(np.asarray(bk), np.asarray(br))

    bk2, wk2 = run_sweeps_multispin(bw.copy(), ww.copy(), beta, 5, seed=2,
                                    block_rows=8, interpret=True)  # donates
    br2, wr2 = ms.run_sweeps_packed(bw, ww, beta, 5, seed=2)  # donates
    np.testing.assert_array_equal(np.asarray(bk2), np.asarray(br2))
    np.testing.assert_array_equal(np.asarray(wk2), np.asarray(wr2))


def test_kernel_physics_lowT():
    """Steady state: an ordered lattice stays ordered under the kernel at
    T=1.5 (cold starts can fall into the striped metastable states the
    paper reports in S5.3, so we start from the ground state)."""
    full = jnp.ones((64, 64), jnp.int8)
    bw, ww = ms.pack_lattice(*lat.split_checkerboard(full))
    beta = jnp.float32(1 / 1.5)
    bw, ww = run_sweeps_multispin(bw, ww, beta, 100, seed=3, block_rows=8,
                                  interpret=True)
    b, w = ms.unpack_lattice(bw, ww)
    m = float(jnp.abs(b.astype(jnp.float32).mean()
                      + w.astype(jnp.float32).mean()) / 2)
    assert m > 0.95
