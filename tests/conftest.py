# NOTE: do NOT set XLA_FLAGS / device-count overrides here — smoke tests and
# benches must see the real single device; only launch/dryrun.py forces 512.
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.RandomState(0)


class _LayerStack:
    """Builds the layer-stacked pools a paged kernel reads: ``stack(pool)``
    puts ``pool`` at index ``layer`` of ``layers`` stacked pools, its
    minor dim padded to ``lanes`` as the model pads it, and fills the rest
    with noise, which a kernel reading the wrong layer or past the real
    width would return."""

    def __init__(self, layers: int, layer: int, lanes: int, rng):
        self.layers, self.layer, self.lanes = layers, layer, lanes
        self._rng = rng

    def __call__(self, pool):
        import jax.numpy as jnp
        d = pool.shape[-1]
        shape = (self.layers,) + pool.shape[:-1] + (max(d, self.lanes),)
        noise = jnp.asarray(self._rng.standard_normal(shape), pool.dtype)
        return noise.at[self.layer, ..., :d].set(pool)


@pytest.fixture(params=[(1, 0, 0), (3, 1, 128)],
                ids=["one_layer", "padded_layer_of_three"])
def stacked(request, rng):
    """Paged kernel cases over a stack of one layer, and over the middle
    layer of three with the minor dim padded to 128 lanes (the model's
    step hands the kernels every layer's pools and the layer to read)."""
    return _LayerStack(*request.param, rng)


def _whole_pool_ops(hlo: str, pool_shapes) -> list:
    """Lines of an optimized HLO module's ENTRY computation (outside every
    loop body) whose result has a whole pool's shape and that copy,
    broadcast or dynamic-update-slice it.  Converts are not counted: the
    CPU backend widens bf16 around some ops, a chip does not."""
    import re
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[:entry.index("\n}\n")]
    found = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                     line)
        if not m or not m.group(2):
            continue
        name, dims, op = m.groups()
        if tuple(map(int, dims.split(","))) not in pool_shapes:
            continue
        what = name if op == "fusion" else op
        if any(k in what.replace("_", "-") for k in
               ("copy", "broadcast", "dynamic-update-slice")):
            found.append(line.strip()[:160])
    return found


@pytest.fixture
def whole_pool_ops():
    """``whole_pool_ops(hlo, pool_shapes)``: the whole-pool copies,
    broadcasts and dynamic-update-slices outside an HLO module's loops."""
    return _whole_pool_ops
