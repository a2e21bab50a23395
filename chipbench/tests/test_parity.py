"""Each configuration's hot start and replay, found through its layout and
stream files, at 64 x 128 on the CPU: bit for bit what the harness drew
and replayed when it knew only the layouts ``int8`` and ``words4`` and
the streams ``site`` and ``word`` in its own code."""
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference as ref
from chipbench import run

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 977
WORKLOADS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]

#: sha256 (first 16 hex digits) of the black and white planes after 2
#: sweeps from sweep 24 and the (M, B) samples after each, replayed in
#: float32 and in the control's bfloat16 by the reference before its
#: layouts and streams became files, on the CPU
PARENT = {
    "words4/word": {
        "float32": ("de5e33edd7adff77", [(248, 7232), (664, 8844)]),
        "bfloat16": ("5984136ab341492b", [(236.0, 7296.0), (640.0, 8832.0)]),
    },
    "int8/site": {
        "float32": ("b5d1eb47d676e520", [(268, 7044), (536, 8696)]),
        "bfloat16": ("0a90c0cbccca9750", [(264.0, 7040.0), (520.0, 8768.0)]),
    },
}


def parent_hot_start(config: dict, seed: int):
    """The hot start as the harness drew it in its own code."""
    n, m, layout = config["n"], config["m"], config["layout"]

    @jax.jit
    def make(key):
        planes = []
        for k in jax.random.split(key):
            if layout == "words4":
                bits = jax.random.bits(k, (n, m // 16), jnp.uint32)
                planes.append(bits & jnp.uint32(0x11111111))
            else:
                bits = jax.random.bits(k, (n, m // 2), jnp.uint8)
                planes.append((2 * (bits & 1) - 1).astype(jnp.int8))
        return planes

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0x7FFFFFFF)
    return dict(zip(config["arrays"], make(key)))


def small(name):
    cell = run.load_cell(name)
    cell["config"] = dict(cell["config"], n=64, m=128)
    return cell


@pytest.mark.parametrize("name", WORKLOADS)
def test_hot_start_as_before(name):
    cell = small(name)
    got = run._hot_start(cell["config"], cell["layout"], SEED)
    want = parent_hot_start(cell["config"], SEED)
    assert list(got) == list(want) == cell["config"]["arrays"]
    for k in want:
        assert got[k].dtype == want[k].dtype
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]))


@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", WORKLOADS)
def test_replay_as_before(name, precision):
    cell = small(name)
    config, layout = cell["config"], cell["layout"]
    assert layout.LATTICES == 1
    state = run._hot_start(config, layout, SEED).values()
    b0, w0 = ([layout.plane(a, 0)] for a in state)
    rb, rw, samples = ref.sweeps(
        b0, w0, temperature=config["temperature"], seed=SEED, step0=24,
        n_sweeps=2, stream=cell["stream"], precision=precision,
        observe_every=1)
    digest = hashlib.sha256(np.asarray(rb[0]).tobytes()
                            + np.asarray(rw[0]).tobytes()
                            + repr([s[0] for s in samples]).encode())
    want = PARENT[f"{config['layout']}/{config['stream']}"][precision]
    assert (digest.hexdigest()[:16], [s[0] for s in samples]) == want
