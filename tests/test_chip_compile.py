"""Ahead-of-time compiles for a described TPU v5e, at real widths.

Nothing runs: each case lowers a kernel (or the whole serving decode
step) for one chip of a described ``v5e:2x2`` topology and asserts that
the compiled program holds a Mosaic kernel (``tpu_custom_call``), i.e.
that the chip's compiler accepts the kernel and that the TPU branch
never falls back to the Pallas interpreter.  The topology is described
inside a fixture, never at import, so only the worker that runs this
file loads the TPU compiler.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BATCH, MAX_SEQ, BLOCK = 8, 2048, 16
NUM_BLOCKS = 1 + (BATCH + 4) * MAX_SEQ // BLOCK


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _custom_calls(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def _pools(cfg, stack: str, num_blocks: int) -> tuple:
    """Shapes of the stacked pools a kernel case reads one layer of, as
    the model makes them: one layer, or the repeats of the config's
    deepest layer group."""
    from repro.models import model as MD
    cache = jax.eval_shape(functools.partial(
        MD.init_paged_cache, cfg, 1, BLOCK, num_blocks, BLOCK))
    deepest = max((v for k, v in cache.items() if k.startswith("g")),
                  key=lambda g: jax.tree.leaves(g)[0].shape[0])
    return tuple((1,) + leaf.shape[1:] if stack == "one_layer"
                 else leaf.shape for leaf in jax.tree.leaves(deepest))


# the model's step hands the kernels every layer's pools and the index of
# the layer to read; one layer is the smallest such stack
STACKS = pytest.mark.parametrize("stack", ["one_layer", "all_layers"])


def _gqa_args(sh, cfg_name, W, stack):
    from repro.configs import get_config
    cfg = get_config(cfg_name)
    kshape, vshape = _pools(cfg, stack, NUM_BLOCKS)
    return (_shape(sh, (BATCH, W, cfg.n_heads, cfg.head_dim)),
            _shape(sh, kshape), _shape(sh, vshape),
            _shape(sh, (BATCH, MAX_SEQ // BLOCK), jnp.int32),
            _shape(sh, (BATCH,), jnp.int32), _shape(sh, (), jnp.int32))


@STACKS
@pytest.mark.parametrize("arch", ["smollm-360m", "qwen3-1.7b"])
@pytest.mark.parametrize("W", [1, 9], ids=["decode", "verify9"])
def test_paged_gqa_kernel_compiles(one_chip, arch, W, stack):
    from repro.kernels.flash_decode import paged_flash_verify
    assert _custom_calls(paged_flash_verify,
                         *_gqa_args(one_chip, arch, W, stack)) >= 1


@STACKS
@pytest.mark.parametrize("W", [1, 9], ids=["decode", "verify9"])
def test_paged_mla_kernel_compiles(one_chip, W, stack):
    """deepseek-v2-236b's published latent widths: H=128, r=512, rh=64
    (verify at a 1024-token table keeps the case near two seconds)."""
    from repro.configs import get_config
    from repro.kernels.flash_decode import paged_flash_verify_mla
    cfg = get_config("deepseek-v2-236b")
    H, r, rh = cfg.n_heads, cfg.kv_lora_rank, cfg.rope_head_dim
    assert (H, r, rh) == (128, 512, 64)
    seq = MAX_SEQ if W == 1 else 1024
    nb = 1 + (BATCH + 4) * seq // BLOCK
    fn = functools.partial(paged_flash_verify_mla,
                           scale=1.0 / (cfg.nope_head_dim
                                        + cfg.rope_head_dim) ** 0.5)
    ckv, krope = _pools(cfg, stack, nb)
    args = (_shape(one_chip, (BATCH, W, H, r)),
            _shape(one_chip, (BATCH, W, H, rh)),
            _shape(one_chip, ckv), _shape(one_chip, krope),
            _shape(one_chip, (BATCH, seq // BLOCK), jnp.int32),
            _shape(one_chip, (BATCH,), jnp.int32),
            _shape(one_chip, (), jnp.int32))
    assert _custom_calls(fn, *args) >= 1


def test_flash_attention_prefill_compiles(one_chip):
    from repro.configs import get_config
    from repro.kernels.flash_attention import flash_attention
    cfg = get_config("smollm-360m")
    S = 512
    q = _shape(one_chip, (1, S, cfg.n_heads, cfg.head_dim))
    kv = _shape(one_chip, (1, S, cfg.n_kv_heads, cfg.head_dim))
    fn = functools.partial(flash_attention, causal=True)
    assert _custom_calls(fn, q, kv, kv) >= 1


def test_full_width_flash_paged_decode_step_holds_kernels(one_chip):
    """The serving decode step of smollm-360m at published widths with
    ``decode_impl="flash_paged"``: the kernel must be compiled into the
    step (the interpreter would lower to plain XLA ops instead)."""
    from repro.configs import get_config
    from repro.models import model as MD
    from repro.serving.serve_lib import make_decode_fn
    cfg = get_config("smollm-360m").replace(decode_impl="flash_paged")
    place = lambda t: jax.tree.map(
        lambda s: _shape(one_chip, s.shape, s.dtype), t)
    params = place(jax.eval_shape(
        functools.partial(MD.init_params, cfg), jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(functools.partial(
        MD.init_paged_cache, cfg, BATCH, MAX_SEQ, NUM_BLOCKS, BLOCK)))
    toks = _shape(one_chip, (BATCH, 1), jnp.int32)
    assert _custom_calls(make_decode_fn(cfg), params, toks, cache) >= 1


def _pool_shapes(cache) -> set:
    return {leaf.shape for k, v in cache.items() if k.startswith("g")
            for leaf in jax.tree.leaves(v)}


@pytest.mark.parametrize("step", ["decode", "prefill_fresh"])
def test_full_width_paged_steps_update_the_pools_in_place(one_chip, step,
                                                          whole_pool_ops):
    """smollm-360m's flash_paged steps at published widths: its 64-wide
    heads sit in pools padded to 128 lanes, whose default layout on the
    chip is row-major, and no whole-pool copy, broadcast or
    dynamic-update-slice runs outside the layer loop."""
    from repro.configs import get_config
    from repro.models import model as MD
    from repro.serving import serve_lib
    cfg = get_config("smollm-360m").replace(decode_impl="flash_paged")
    fresh, _, decode, _ = serve_lib.build_paged_serve_steps(cfg)
    place = lambda t: jax.tree.map(                          # noqa: E731
        lambda s: _shape(one_chip, s.shape, s.dtype), t)
    params = place(jax.eval_shape(
        functools.partial(MD.init_params, cfg), jax.random.PRNGKey(0)))
    shapes = jax.eval_shape(functools.partial(
        MD.init_paged_cache, cfg, BATCH, MAX_SEQ, NUM_BLOCKS, BLOCK))
    i32 = lambda *s: _shape(one_chip, s, jnp.int32)          # noqa: E731
    if step == "decode":
        lowered = decode.lower(params, i32(BATCH, 1), place(shapes))
    else:
        lowered = fresh.lower(params, i32(1, 64), i32(1), i32(1),
                              i32(1, MAX_SEQ // BLOCK), place(shapes))
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert whole_pool_ops(hlo, _pool_shapes(shapes)) == []
    assert "tpu_custom_call" in hlo or step != "decode"
    pools = jax.tree.leaves(compiled.input_formats[0][-1]["g0"])
    assert all(f.layout.major_to_minor == tuple(range(5)) for f in pools)


def test_four_way_paged_decode_updates_the_pools_in_place(topo,
                                                          whole_pool_ops):
    """glm4-9b's XLA paged decode at published widths, 4-way over the
    mesh ``model`` axis as the fleet builds it: the pools stay where the
    compiler places them for the sharded step, with no whole-pool copy
    outside the layer loop and no collective over a pool."""
    import re

    import numpy as np
    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.models import model as MD
    from repro.serving import serve_lib
    from repro.sharding import rules as R
    from repro.sharding.ctx import sharding_rules
    cfg = get_config("glm4-9b").replace(decode_impl="xla")
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    batch, blocks = 32, 1 + 36 * MAX_SEQ // BLOCK
    with sharding_rules(mesh, R.act_rules(mesh, batch)):
        sh = serve_lib.serve_shardings(cfg, mesh, batch, MAX_SEQ)
        params = jax.tree.map(
            lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=p),
            sh["params_shape"], sh["param_sharding"])
        cache = jax.eval_shape(functools.partial(
            MD.init_paged_cache, cfg, batch, MAX_SEQ, blocks, BLOCK))
        decode = serve_lib.build_paged_serve_steps(cfg)[2]
        compiled = decode.lower(
            params, jax.ShapeDtypeStruct((batch, 1), jnp.int32),
            cache).compile()
    hlo = compiled.as_text()
    local = {s.shard_shape(c.shape) for s, c in zip(
        jax.tree.leaves(compiled.input_shardings[0][2]["g0"]),
        jax.tree.leaves(cache["g0"]))}
    assert whole_pool_ops(hlo, local | _pool_shapes(cache)) == []
    collectives = re.findall(
        r"= \w+\[([\d,]*)\]\S* (?:all-gather|all-to-all|collective-permute)",
        hlo)
    assert not [d for d in collectives if str(blocks) in d.split(",")]
