"""A new architecture comes to the harness as files alone.

The stand-in under ``standin/`` is a benchmark directory of its own: a
configuration naming the program's reduced deepseek-v2-236b (latent
attention, routed and shared experts, a dense first layer), a traffic
mix, and ``families/mla_moe.py``.  Copied under a temporary directory with
a ``BENCHMARK.json`` of one cell, it is found, built, checked, weighted,
warmed up, served through an open-loop window and counted by the
harness's own functions, with no file of the harness changed.
"""

import json
import shutil
from types import SimpleNamespace

import chipbench_testlib as TL
import jax
import numpy as np
import pytest

from harness import cell as C
from harness import check, spec, system
from harness import trace as T
from harness import weights as W
from harness.traffic import generate

SEED = 2**33 + 5
SECONDS = 3.0
CELL = "dsv2.chat"


@pytest.fixture(scope="module")
def standin(tmp_path_factory):
    root = tmp_path_factory.mktemp("standin")
    bdir = root / "bench"
    shutil.copytree(TL.BENCH / "tests" / "standin", bdir)
    (root / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": CELL, "config": "dsv2-standin",
                       "traffic": "chat", "chips": 1, "why": "stand-in"}],
        "end_to_end": [{"name": "setup_s"}], "per_layer": []}))
    cell = spec.load_cell(CELL, root=root, bench_dir=bdir)
    bench = TL.prepare_small(cell, SEED)
    sched = generate(cell.traffic, cell.config, SEED, SECONDS)
    system.warm_up(bench.fleet, cell.config, cell.traffic, sched)
    return bench, sched


def test_the_family_is_the_configurations(standin):
    bench, _ = standin
    assert bench.cell.family.__file__.endswith("families/mla_moe.py")
    member = bench.fleet.members["deepseek-v2-236b"]
    assert member.cfg.is_mla and member.cfg.n_experts
    want = jax.jit(lambda k: bench.cell.family.program_params(
        bench.cell.config["models"]["dsv2"], k, jax.numpy.bfloat16))(
            W.member_key(SEED, "dsv2"))
    for a, b in zip(jax.tree.leaves(member.params), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_window_is_served_without_a_failure(standin):
    bench, sched = standin
    win = C.run_window(bench, sched, drain_s=60.0)
    assert win.drained and len(win.records) == len(sched.requests) > 0
    assert check.failures(win.records, sched.gen_tokens) == []
    assert check.route_mismatches(win.records,
                                  bench.cell.config["router"]) == []


def test_the_readers_count_through_the_family(standin):
    bench, _ = standin
    m = bench.cell.config["models"]["dsv2"]
    fam = bench.cell.family
    E = T.Event
    peak = {"bf16_flops": 1e9, "hbm_bytes_per_s": 1e9}
    tr = T.Trace({0: [E("_paged_kernel.1", 0, 0.5)]},
                 {0: [E("jit_serve_step(1)", 0, 2)]}, [], (0.0, 4.0))
    ctx = SimpleNamespace(trace=tr, decode_calls=[("deepseek-v2-236b", [5, 9])],
                          models_by_arch=bench.models_by_arch,
                          family_of=bench.family_of, chips=1, block_tokens=16,
                          peak=peak)
    # one decode program of 2 s, one kernel call of 0.5 s
    assert spec.load_reader("step.decode_mfu").read(ctx) == pytest.approx(
        100.0 * fam.decode_step_flops(m, [5, 9]) / (2.0 * 1e9))
    fl, by = fam.paged_attention_cost(m, [5, 9], 16)
    assert spec.load_reader("kernel.paged_attn_roofline").read(ctx) == \
        pytest.approx(100.0 * max(fl, by) / 1e9 / 0.5)


def test_a_model_the_program_does_not_run_is_refused(standin):
    bench, _ = standin
    m = dict(bench.cell.config["models"]["dsv2"], first_k_dense_replace=2)
    with pytest.raises(spec.ConfigMismatch):
        bench.cell.family.check(bench.fleet.members["deepseek-v2-236b"].cfg,
                                m, "deepseek-v2-236b")
