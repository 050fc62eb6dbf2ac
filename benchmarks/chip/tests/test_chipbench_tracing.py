"""The trace reduction on a small made-up trace with known shares."""

import chipbench_testlib as TL
import pytest
from types import SimpleNamespace

from harness import spec
from harness import trace as T

E = T.Event


def made_up():
    # window [0, 10) s on two devices
    ops = {0: [E("fusion.1", 0, 2), E("_paged_kernel.3", 2, 3),
               E("all-reduce.7", 3, 4), E("fusion.2", 3.5, 5),
               E("all-gather.1", 6, 7)],
           1: [E("fusion.1", 0, 4), E("_paged_kernel.3", 5, 6),
               E("all-reduce.7", 6, 8)]}
    mods = {0: [E("jit_serve_step(1)", 0, 5), E("jit_prefill_paged(2)", 6, 7)],
            1: [E("jit_serve_step(1)", 0, 6), E("jit_prefill_paged(2)", 6, 8)]}
    host = [E("bench:window", 0, 10), E("bench:router.route", 0, 10),
            E("bench:lane.step:a", 7.2, 9.8), E("bench:decode:a", 7.5, 9.5)]
    return T.Trace(ops, mods, host, (0.0, 10.0))


def test_busy_and_idle_share():
    tr = made_up()
    # device 0 busy [0,5) + [6,7) = 6 s; device 1 [0,4) + [5,8) = 7 s
    assert T.busy_s(tr) == pytest.approx(6.5)
    assert T.idle_share(tr) == pytest.approx(0.35)


def test_kernel_and_module_time():
    tr = made_up()
    assert T.per_device_time(tr, "paged_kernel", modules=False) == \
        pytest.approx(1.0)
    assert T.count(tr, "paged_kernel", modules=False) == 1
    assert T.per_device_time(tr, "prefill_paged", modules=True) == \
        pytest.approx(1.5)
    assert T.per_device_time(tr, "serve_step", modules=True) == \
        pytest.approx(5.5)


def test_clip_to_window():
    tr = made_up()
    tr.window = (1.0, 3.5)
    c = tr.clip()
    assert c.window_s == pytest.approx(2.5)
    assert T.busy_s(c) == pytest.approx((2.5 + 2.5) / 2)


def test_breakdown_names_gaps_by_the_host_span():
    tr = made_up()
    gaps = T.idle_gaps(tr)
    assert gaps[0] == ["bench:decode:a", pytest.approx(3.0)]
    assert gaps[1] == ["bench:router.route", pytest.approx(1.0)]
    top = dict((k, v) for k, v in T.top_ops(tr))
    assert top["fusion"] == pytest.approx((3.5 + 4) / 2)
    assert len(T.top_ops(tr, 2)) == 2


@pytest.mark.parametrize("name,want", [
    ("device.idle_share", 35.0),
    ("kernel.paged_attn_roofline", None),
])
def test_readers_on_the_made_up_trace(name, want):
    ctx = SimpleNamespace(trace=made_up(), decode_calls=[], prefill_calls=[])
    got = spec.load_reader(name).read(ctx)
    assert got == (pytest.approx(want) if want is not None else None)


def test_decode_readers_from_recorded_work():
    fam = TL.DENSE
    m = fam.entry_for(TL.dense_cfg(2, d_model=8, n_heads=4, n_kv_heads=2,
                                   head_dim=2, d_ff=12, vocab_size=10))
    peak = {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e3}
    ctx = SimpleNamespace(trace=made_up(), decode_calls=[("a", [4, 4])],
                          prefill_calls=[("a", 100), ("a", 300)],
                          models_by_arch={"a": m},
                          family_of={"a": fam}.__getitem__, block_tokens=4,
                          chips=2, peak=peak)
    fl = fam.decode_step_flops(m, [4, 4])
    assert spec.load_reader("step.decode_mfu").read(ctx) == \
        pytest.approx(100 * fl * 1 / (5.5 * 2 * 1e3))
    # 1.5 s of prefill for 1 call of 200 tokens on average
    assert spec.load_reader("step.prefill_ms_per_ktok").read(ctx) == \
        pytest.approx(1e3 * 1.5 / 0.2)
    afl, aby = fam.paged_attention_cost(m, [4, 4], 4)
    least = max(afl / 1e3, aby / 1e3)
    assert spec.load_reader("kernel.paged_attn_roofline").read(ctx) == \
        pytest.approx(100 * least * 1 / 1.0)
