"""The paged steps update the KV block pools in place.

Each layer of the step's layer scan reads and writes the pools stacked
over layers, which ride in the scan's carry: no step restacks, copies or
rebuilds a whole pool.  Checked on the optimized program the CPU backend
compiles for a reduced configuration (the chip's own compiler is checked
at published widths in ``test_chip_compile.py``).
"""

import functools

import jax
import jax.numpy as jnp
import pytest

SLOTS, MAX_SEQ, BLOCK, WIDTH = 3, 64, 16, 32


def _paged_step(arch, decode_impl, step):
    """Optimized HLO of one paged step as the fleet builds it, and the
    shapes of the cache's pools."""
    from repro.configs import get_reduced
    from repro.models import model as MD
    from repro.serving import serve_lib
    cfg = get_reduced(arch).replace(decode_impl=decode_impl)
    fresh, _, decode, _ = serve_lib.build_paged_serve_steps(cfg)
    params = jax.eval_shape(functools.partial(MD.init_params, cfg),
                            jax.random.PRNGKey(0))
    num_blocks = 1 + (SLOTS + 1) * MAX_SEQ // BLOCK
    cache = jax.eval_shape(functools.partial(
        MD.init_paged_cache, cfg, SLOTS, MAX_SEQ, num_blocks, BLOCK))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)   # noqa: E731
    if step == "decode":
        lowered = decode.lower(params, i32(SLOTS, 1), cache)
    else:
        lowered = fresh.lower(params, i32(1, WIDTH), i32(1), i32(1),
                              i32(1, MAX_SEQ // BLOCK), cache)
    pools = {leaf.shape for k, v in cache.items() if k.startswith("g")
             for leaf in jax.tree.leaves(v)}
    return lowered.compile().as_text(), pools


@pytest.mark.parametrize("step", ["decode", "prefill_fresh"])
@pytest.mark.parametrize("decode_impl", ["xla", "flash_paged"])
def test_paged_step_never_copies_a_whole_pool(decode_impl, step,
                                              whole_pool_ops):
    hlo, pools = _paged_step("smollm-360m", decode_impl, step)
    assert whole_pool_ops(hlo, pools) == []
