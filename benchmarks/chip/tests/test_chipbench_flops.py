"""FLOP and byte counts against hand counts at a small shape, and at the
published widths against the numbers they have always been."""

import chipbench_testlib as TL
import pytest

from harness import flops as F
from harness import spec
from harness.peaks import peaks_for

FAM = TL.DENSE
# 2 layers, width 8, 4 heads of 2 over 2 KV heads, MLP 12, vocab 10
M = FAM.entry_for(TL.dense_cfg(2, d_model=8, n_heads=4, n_kv_heads=2,
                               head_dim=2, d_ff=12, vocab_size=10))


def test_params_by_hand():
    # q 8*8, k 8*4, v 8*4, o 8*8, mlp 3*8*12
    assert FAM.layer_matmul_params(M) == 64 + 32 + 32 + 64 + 288
    assert FAM.matmul_params(M) == 2 * 480 + 10 * 8


def test_decode_flops_by_hand():
    # per token 2*1040, attention 2 layers * 4 (qk + pv) * 4 heads * 2 * kv
    assert FAM.attention_flops(M, 5) == 2 * 4 * 4 * 2 * 5
    assert FAM.decode_step_flops(M, [5, 1]) == \
        2 * (2 * 1040) + 2 * 4 * 4 * 2 * (5 + 1)


def test_paged_attention_cost_by_hand():
    fl, by = FAM.paged_attention_cost(M, [5, 17], block_tokens=4)
    assert fl == 4 * 4 * 2 * (5 + 17)
    # per row: q + out 2*4*2*2 bytes; pages ceil(5/4)=2, ceil(17/4)=5 of
    # 4 tokens x 2 kv heads x 2 dims x 2 bytes, keys and values
    assert by == 2 * (2 * 4 * 2 * 2) + 2 * 2 * (2 + 5) * 4 * 2 * 2
    assert FAM.attention_layers(M) == 2


# decode_step_flops and paged_attention_cost (16-token pages) of each
# published model at kv lengths (1, 385, 2047), as the harness counted
# them before each architecture's counts moved into its family.
PINNED = {
    ("mom-qwen3-smollm", "qwen3"): (10880778240, (19931136, 10117120)),
    ("mom-qwen3-smollm", "smollm"): (2469519360, (9342720, 3165440)),
    ("glm4-9b-tp4", "glm4"): (54266560512, (39862272, 2572288)),
}


@pytest.mark.parametrize("config,model", sorted(PINNED))
def test_counts_at_published_widths_equal_the_pinned_numbers(config, model):
    cfg = spec.load_config(config)
    fam = spec.load_family(cfg["reference"])
    m = cfg["models"][model]
    lens = (1, 385, 2047)
    assert (fam.decode_step_flops(m, lens),
            fam.paged_attention_cost(m, lens, 16)) == PINNED[config, model]


def test_roofline_takes_the_larger_bound():
    pk = peaks_for("TPU v5 lite")
    assert F.roofline_seconds(197e12, 0, pk) == pytest.approx(1.0)
    assert F.roofline_seconds(1, 819e9, pk) == pytest.approx(1.0)
    assert F.roofline_seconds(197e12, 2 * 819e9, pk) == pytest.approx(2.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("cpu")
