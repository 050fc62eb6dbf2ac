"""The plain reference against the program's own forward pass, and its
int8 control against the served bfloat16 precision, at small sizes."""

import chipbench_testlib as TL
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import check, spec
from harness import weights as W

REF = spec.load_reference("dense_gqa")
FAM = TL.DENSE


def program_logits(arch_cfg, m, key, toks, dtype):
    from repro.models import model as MD
    p = jax.jit(lambda k: FAM.program_params(m, k, jnp.bfloat16))(key)
    p = jax.tree.map(lambda a: a.astype(dtype), p)
    cfg = arch_cfg.replace(dtype=jnp.dtype(dtype).name)
    return np.asarray(MD.forward(cfg, p, jnp.asarray(toks)[None])[0][0],
                      np.float32)


@pytest.mark.parametrize("arch", ("qwen3-1.7b", "smollm-360m", "glm4-9b"))
def test_reference_equals_the_program_in_float32(arch):
    """Same weights, same tokens: the reference is the architecture the
    program runs (qk-norm, tied or untied head, GQA, rotary phases)."""
    from repro.configs import get_reduced
    m = TL.small_model(arch)
    key = W.member_key(17, arch)
    toks = np.random.default_rng(1).integers(4, m["vocab_size"], 40)
    pos = np.arange(40)
    ref = REF.Reference(m, key, max_seq=64).logits_at([toks], [pos])[0]
    got = program_logits(get_reduced(arch), m, key, toks, jnp.float32)
    np.testing.assert_allclose(got, ref, atol=2e-5 * np.abs(ref).max())


# Readings at mid_cfg's widths over seeds 0-5 (CPU run): the bfloat16 program's mean
# gap 0.000042-0.000118, the int8 control's 0.00126-0.00296; the limit
# lies between them, with more room above the lower reading.
MID_LIMIT = 0.0004


def mid_cfg():
    """A mid-size dense GQA model the CPU can hold: 4 layers, width 256."""
    return TL.dense_cfg(4, d_model=256, n_heads=8, n_kv_heads=4, head_dim=32,
                        d_ff=512, vocab_size=2048, qk_norm=True,
                        tie_embeddings=True, rope_theta=1e4)


@pytest.mark.parametrize("seed", (0, 1, 2))
def test_int8_control_fails_where_bfloat16_passes(seed):
    mid = FAM.entry_for(mid_cfg())
    key = W.member_key(seed, "mid")
    toks = np.random.default_rng(seed).integers(4, 2048, 256).astype(np.int32)
    pos = np.arange(128, 256)
    ref = REF.Reference(mid, key, max_seq=256).logits_at([toks], [pos])
    low = REF.Reference(mid, key, max_seq=256,
                        mode="int8").logits_at([toks], [pos])
    served = program_logits(mid_cfg(), mid, key, toks, jnp.bfloat16)[pos]
    program = check.mean_gap(ref, [served.argmax(-1)])
    control = check.mean_gap(ref, check.control_tokens(low))
    assert program <= MID_LIMIT < control, (program, control)
