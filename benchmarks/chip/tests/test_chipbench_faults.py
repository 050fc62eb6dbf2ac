"""A run whose timed path is broken underneath must come out not correct.

The harness is driven as a run drives it (its own set-up, open-loop
window, drain and judging), at the program's small presets on the CPU,
with one fault planted at a time where the answer is produced:

- a sound run (the check passes);
- every decoded token altered as the decode step returns it;
- a decode step that returns its KV state unchanged;
- the router deciding by other rules than the configuration states;
- the exchange between tensor-parallel chips left out: each row-parallel
  projection keeps only its first shard's partial sum (what a chip holds
  before the all-reduce), planted in the weights the fleet serves.

And the control: where every served token is the one an int8 path puts
first, the run's own comparison (``judge``) must come out not correct at
a limit that sound runs of the same stand-in pass.
"""

import copy

import chipbench_testlib as TL
import jax
import jax.numpy as jnp
import pytest

from harness import cell as C
from harness import rules, system
from harness.traffic import generate

SEED = 2**32 + 11
SECONDS = 3.0


@pytest.fixture(scope="module")
def chat():
    cell = TL.small_cell("mom.chat_poisson")
    bench = TL.prepare_small(cell, SEED)
    sched = generate(cell.traffic, cell.config, SEED, SECONDS)
    system.warm_up(bench.fleet, cell.config, cell.traffic, sched)
    return bench


def window(bench, seed):
    """A window of traffic drawn from ``seed``, judged against the
    reference made from the weights' seed."""
    cell = bench.cell
    bench.taps.finished.clear()
    sched = generate(cell.traffic, cell.config, seed, SECONDS)
    win = C.run_window(bench, sched, drain_s=60.0)
    return C.judge(bench, win, SEED, free=False)


def alter_tokens(bench):
    undo = []
    for arch, m in bench.fleet.members.items():
        dec, vocab = m.decode_rows, m.cfg.vocab_size

        def bad(*a, dec=dec, vocab=vocab):
            nxt, cache = dec(*a)
            return (nxt + 1) % vocab, cache
        m.decode_rows = bad
        undo.append((m, dec))
    return lambda: [setattr(m, "decode_rows", d) for m, d in undo]


def keep_state(bench):
    undo = []
    for arch, m in bench.fleet.members.items():
        dec = m.decode_rows

        def bad(params, toks, cache, dec=dec):
            nxt, _ = dec(params, toks, jax.tree.map(jnp.copy, cache))
            return nxt, cache
        m.decode_rows = bad
        undo.append((m, dec))
    return lambda: [setattr(m, "decode_rows", d) for m, d in undo]


def other_rules(bench):
    from repro.core.dsl import compile_source
    from repro.core.router import SemanticRouter
    r = copy.deepcopy(bench.cell.config["router"])
    r["signals"]["reasoning"] = r["signals"]["reasoning"][:1]
    cfg, _ = compile_source(rules.render_dsl(r))
    m2a = {m: p["arch"] for m, p in r["profiles"].items()}
    old = bench.router
    bench.router = SemanticRouter(cfg, call_fn=bench.fleet.call_fn(m2a))

    def undo():
        bench.router = old
    return undo


def no_exchange(bench, shards=4):
    for m in bench.fleet.members.values():
        b = m.params["g0"]["b0"]
        for grp, leaf in (("attn", "wo"), ("ffn", "w_down")):
            w = b[grp][leaf]
            keep = w.shape[1] // shards
            b[grp][leaf] = w.at[:, keep:, :].set(0)
    return lambda: system.install_weights(bench.fleet, bench.cell.config,
                                          bench.cell.family, SEED)


def test_sound_run_is_correct(chat):
    ok, checks = window(chat, SEED)
    assert ok, checks


@pytest.mark.parametrize("fault,number", [
    (alter_tokens, "mean_logit_gap"),
    (keep_state, "mean_logit_gap"),
    (other_rules, "route_mismatch"),
    (no_exchange, "mean_logit_gap"),
])
def test_a_broken_run_is_not_correct(chat, fault, number):
    undo = fault(chat)
    try:
        ok, checks = window(chat, SEED + 1)
    finally:
        undo()
    assert not ok, checks
    failed = [k for k, c in checks.items() if c["value"] > c["limit"]]
    assert any(k.startswith(number) for k in failed), checks



# At TL.MID_FIELDS widths (CPU run), over six seeds: the bfloat16 program's mean
# gap read 0.00001-0.00016 a model, the int8 control's 0.00107-0.00273
# (the larger of the two models, which decides the verdict).  The limit
# lies between them, with more room above the lower reading.
MID_LIMIT = 0.0004


@pytest.fixture(scope="module")
def mid():
    cell = TL.mid_cell("mom.chat_poisson", MID_LIMIT)
    bench = TL.prepare_small(cell, SEED, arch_overrides=TL.mid_overrides(cell))
    sched = generate(cell.traffic, cell.config, SEED, SECONDS)
    system.warm_up(bench.fleet, cell.config, cell.traffic, sched)
    return bench


@pytest.mark.parametrize("seed", (SEED + 100, SEED + 101, SEED + 102))
def test_the_int8_control_is_not_correct(mid, seed):
    cell = mid.cell
    system.install_weights(mid.fleet, cell.config, cell.family, seed)
    mid.taps.finished.clear()
    win = C.run_window(mid, generate(cell.traffic, cell.config, seed,
                                     SECONDS), drain_s=60.0)
    refs = C.correctness(mid, win, seed, modes=("f32", "int8"))
    ok, checks = C.judge(mid, win, seed, free=False, refs=refs)
    assert ok, checks
    ok, checks = C.judge(mid, win, seed, free=False, refs=refs, control=True)
    assert not ok, checks
