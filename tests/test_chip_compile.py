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
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _custom_calls(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def _gqa_args(sh, cfg_name, W):
    from repro.configs import get_config
    cfg = get_config(cfg_name)
    pool = _shape(sh, (NUM_BLOCKS, cfg.n_kv_heads, BLOCK, cfg.head_dim))
    return (_shape(sh, (BATCH, W, cfg.n_heads, cfg.head_dim)), pool, pool,
            _shape(sh, (BATCH, MAX_SEQ // BLOCK), jnp.int32),
            _shape(sh, (BATCH,), jnp.int32))


@pytest.mark.parametrize("arch", ["smollm-360m", "qwen3-1.7b"])
@pytest.mark.parametrize("W", [1, 9], ids=["decode", "verify9"])
def test_paged_gqa_kernel_compiles(one_chip, arch, W):
    from repro.kernels.flash_decode import paged_flash_verify
    assert _custom_calls(paged_flash_verify,
                         *_gqa_args(one_chip, arch, W)) >= 1


@pytest.mark.parametrize("W", [1, 9], ids=["decode", "verify9"])
def test_paged_mla_kernel_compiles(one_chip, W):
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
    args = (_shape(one_chip, (BATCH, W, H, r)),
            _shape(one_chip, (BATCH, W, H, rh)),
            _shape(one_chip, (nb, BLOCK, r)),
            _shape(one_chip, (nb, BLOCK, rh)),
            _shape(one_chip, (BATCH, seq // BLOCK), jnp.int32),
            _shape(one_chip, (BATCH,), jnp.int32))
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
