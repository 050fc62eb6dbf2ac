"""Seeded weights: the whole tree and any one layer or block of rows made
alone agree bit for bit, they fit the program's parameter tree, and they
are the bits they have always been."""

import hashlib

import chipbench_testlib as TL
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import spec, system
from harness import weights as W

ARCHS = ("qwen3-1.7b", "smollm-360m", "glm4-9b")
FAM = TL.DENSE


@pytest.mark.parametrize("arch", ARCHS)
def test_layer_and_rows_made_alone_match_the_tree(arch):
    m = TL.small_model(arch)
    key = W.member_key(2**40 + 3, "x")
    full = jax.jit(lambda k: FAM.all_weights(m, k))(key)
    for layer in range(m["num_hidden_layers"]):
        one = jax.jit(lambda k, l: FAM.layer_weights(m, k, l))(key, layer)
        for name, leaf in one.items():
            np.testing.assert_array_equal(np.asarray(full["layers"][name][layer]),
                                          np.asarray(leaf))
    rows = FAM.vocab_rows(m, key, "embed", 1, 1)
    np.testing.assert_array_equal(np.asarray(full["embed"][128:256]),
                                  np.asarray(rows))
    assert full["embed"].dtype == jnp.bfloat16


@pytest.mark.parametrize("arch", ARCHS)
def test_program_params_fit_the_program_tree(arch):
    from repro.configs import get_reduced
    from repro.models import model as MD
    m = TL.small_model(arch)
    mine = jax.eval_shape(lambda k: FAM.program_params(m, k, jnp.bfloat16),
                          W.member_key(1, "x"))
    theirs = jax.eval_shape(lambda k: MD.init_params(get_reduced(arch), k),
                            jax.random.PRNGKey(0))
    assert system._shape_tree(mine) == system._shape_tree(theirs)
    FAM.check(get_reduced(arch), m, arch)


def test_seeds_past_64_bits_and_negative_are_keys():
    for s in (0, -1, 2**31 + 9, 2**70 + 1):
        assert W.seed_key(s).shape == ()
    m = TL.small_model("qwen3-1.7b")
    a = FAM.layer_weights(m, W.member_key(5, "a"), 0)
    b = FAM.layer_weights(m, W.member_key(6, "a"), 0)
    assert all(not np.array_equal(np.asarray(a[n]), np.asarray(b[n]))
               for n in a)


def test_a_model_the_program_does_not_run_is_refused():
    from repro.configs import get_reduced
    m = dict(TL.small_model("qwen3-1.7b"), rms_norm_eps=1e-5)
    with pytest.raises(spec.ConfigMismatch):
        FAM.check(get_reduced("qwen3-1.7b"), m, "qwen3-1.7b")


def digest(tree) -> str:
    h = hashlib.sha256()
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(flat, key=lambda x: jax.tree_util.keystr(x[0])):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:24]


# Digests of the weights at the reduced presets, member key (2**40 + 7,
# arch), as the harness made them before each architecture's layout moved
# into its family: the program's tree, and the reference's layer 0 and
# first chunk of vocabulary rows (embedding, and the output head where it
# is not tied).
PINNED = {
    "qwen3-1.7b": ("540169e33cac19b868a78444", "a1af4feeda19cadda11c5f99",
                   "da201e49b3ad339cfe807d42"),
    "smollm-360m": ("eada80455a9e2bfe8976f7df", "f8d88f8ebffe2ac46793deec",
                    "cd78211113039eb8a38ccc0e"),
    "glm4-9b": ("d9ae46f00cb159009ba32065", "92db5867c8da55fbd86129bf",
                "8e1ad1c68503196487e65acc"),
}


@pytest.mark.parametrize("arch", ARCHS)
def test_weights_equal_the_pinned_digests(arch):
    m = TL.small_model(arch)
    key = W.member_key(2**40 + 7, arch)
    params = jax.jit(lambda k: FAM.program_params(m, k, jnp.bfloat16))(key)
    ref = spec.load_reference("dense_gqa").Reference(m, key, max_seq=64)
    rows = {"embed": ref._rows("embed", 0)}
    if not m["tie_word_embeddings"]:
        rows["lm_head"] = ref._rows("lm_head", 0)
    assert (digest(params), digest(ref._layer_w(0)), digest(rows)) == \
        PINNED[arch]
