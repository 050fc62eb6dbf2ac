"""Infrastructure: checkpoint save/restore (+elastic path), sharding-rule
validity across every arch, HLO cost parser, data determinism, gradient
compression, roofline math, observability."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.configs import get_config, list_archs
from repro.data.pipeline import TokenStream
from repro.models import model as MD
from repro.roofline.analysis import Roofline
from repro.roofline.hlo_cost import analyze
from repro.sharding import rules as R


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "nested": {"b": jnp.ones((5,), jnp.bfloat16),
                       "step": jnp.asarray(7, jnp.int32)}}
    save_checkpoint(str(tmp_path), 10, tree, {"note": "x"})
    assert latest_step(str(tmp_path)) == 10
    target = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
    restored, meta = restore_checkpoint(str(tmp_path), 10, target)
    assert meta["note"] == "x"
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_atomicity(tmp_path):
    tree = {"a": jnp.ones((4,))}
    save_checkpoint(str(tmp_path), 1, tree)
    save_checkpoint(str(tmp_path), 2, tree)
    # a stray .tmp dir (crash mid-save) must not be picked up
    os.makedirs(str(tmp_path / "step_00000099.tmp"))
    assert latest_step(str(tmp_path)) == 2


# ---------------------------------------------------------------------------
# sharding rules: every arch x both mesh shapes produce valid, divisible specs
# ---------------------------------------------------------------------------

class FakeMesh:
    def __init__(self, shape_dict):
        self.shape = shape_dict
        self.axis_names = tuple(shape_dict)


MESHES = [FakeMesh({"data": 16, "model": 16}),
          FakeMesh({"pod": 2, "data": 16, "model": 16})]


def _axis_size(mesh, ax):
    if isinstance(ax, tuple):
        n = 1
        for a in ax:
            n *= mesh.shape[a]
        return n
    return mesh.shape[ax]


@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("mesh", MESHES, ids=["pod", "multipod"])
def test_param_and_cache_specs_valid(arch, mesh):
    cfg = get_config(arch)
    pshape = jax.eval_shape(lambda: MD.init_params(cfg,
                                                   jax.random.PRNGKey(0)))
    specs = R.param_specs(cfg, pshape, mesh)
    flat_p = jax.tree.leaves(pshape)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: x is None or
                             hasattr(x, "index"))
    assert len(flat_p) == len(flat_s)
    for leaf_shape, spec in zip(flat_p, flat_s):
        assert len(spec) <= len(leaf_shape.shape)
        for dim, ax in zip(leaf_shape.shape, tuple(spec)):
            if ax is not None:
                assert dim % _axis_size(mesh, ax) == 0, \
                    (arch, leaf_shape.shape, tuple(spec))

    cshape = jax.eval_shape(lambda: MD.init_cache(cfg, 128, 1024))
    cspecs = R.cache_specs(cfg, cshape, mesh)
    for leaf_shape, spec in zip(jax.tree.leaves(cshape),
                                jax.tree.leaves(cspecs,
                                                is_leaf=lambda x: hasattr(
                                                    x, "index"))):
        for dim, ax in zip(leaf_shape.shape, tuple(spec)):
            if ax is not None:
                assert dim % _axis_size(mesh, ax) == 0, \
                    (arch, leaf_shape.shape, tuple(spec))


def test_specs_degrade_for_batch_one():
    mesh = MESHES[0]
    assert tuple(R.batch_spec(mesh, 1)) == (None, None)
    assert tuple(R.batch_spec(mesh, 128))[0] == "data"


# ---------------------------------------------------------------------------
# HLO cost parser
# ---------------------------------------------------------------------------

def test_hlo_cost_scan_multiplier():
    def f(x, w):
        def body(h, _):
            return h @ w, None
        h, _ = jax.lax.scan(body, x, None, length=8)
        return h
    c = jax.jit(f).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32),
                         jax.ShapeDtypeStruct((64, 64), jnp.float32)) \
        .compile()
    r = analyze(c.as_text())
    assert r["flops"] == pytest.approx(2 * 64 * 64 * 64 * 8, rel=0.01)


def test_hlo_cost_nested_scan():
    def f(x, w):
        def outer(h, _):
            def inner(g, _):
                return g @ w, None
            g, _ = jax.lax.scan(inner, h, None, length=3)
            return g, None
        h, _ = jax.lax.scan(outer, x, None, length=4)
        return h
    c = jax.jit(f).lower(jax.ShapeDtypeStruct((32, 32), jnp.float32),
                         jax.ShapeDtypeStruct((32, 32), jnp.float32)) \
        .compile()
    r = analyze(c.as_text())
    assert r["flops"] == pytest.approx(2 * 32 * 32 * 32 * 12, rel=0.01)


def test_hlo_collective_parse_synthetic():
    hlo = """
HloModule m, entry_computation_layout={()->f32[]}

%region_cond (p: (s32[], f32[128,256])) -> pred[] {
  %p = (s32[], f32[128,256]{1,0}) parameter(0)
  %c = s32[] constant(5)
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%region_body (p: (s32[], f32[128,256])) -> (s32[], f32[128,256]) {
  %p.1 = (s32[], f32[128,256]{1,0}) parameter(0)
  %x = f32[128,256]{1,0} get-tuple-element(%p.1), index=1
  %ag = f32[256,256]{1,0} all-gather(%x), replica_groups=[8,2]<=[16], dimensions={0}
  %i.1 = s32[] get-tuple-element(%p.1), index=0
  %one = s32[] constant(1)
  %i2 = s32[] add(%i.1, %one)
  ROOT %t = (s32[], f32[128,256]{1,0}) tuple(%i2, %x)
}

ENTRY %main () -> f32[] {
  %init = (s32[], f32[128,256]{1,0}) tuple()
  %w = (s32[], f32[128,256]{1,0}) while(%init), condition=%region_cond, body=%region_body
  ROOT %r = f32[] constant(0)
}
"""
    r = analyze(hlo)
    ag = r["collectives"]["all-gather"]
    assert ag["count"] == 5                       # x5 loop trips
    assert ag["bytes"] == 5 * 128 * 256 * 4


# ---------------------------------------------------------------------------
# data pipeline determinism
# ---------------------------------------------------------------------------

def test_tokenstream_deterministic_and_learnable():
    s1 = TokenStream(1000, 4, 64, seed=3)
    s2 = TokenStream(1000, 4, 64, seed=3)
    a1, b1 = s1.batch_at(17)
    a2, b2 = s2.batch_at(17)
    np.testing.assert_array_equal(a1, a2)
    np.testing.assert_array_equal(b1, b2)
    a3, _ = s1.batch_at(18)
    assert not np.array_equal(a1, a3)
    assert b1.shape == a1.shape == (4, 64)
    np.testing.assert_array_equal(a1[:, 1:], b1[:, :-1])  # shifted labels


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_int8_quantization_error_bound():
    from repro.distributed.compression import _dequantize, _quantize, \
        compression_ratio
    key = jax.random.PRNGKey(0)
    g = jax.random.normal(key, (1000,)) * 0.01
    q, scale, pad = _quantize(g, key)
    back = _dequantize(q, scale, pad, g.shape, g.dtype)
    err = float(jnp.abs(back - g).max())
    assert err <= float(scale.max()) * 1.0 + 1e-9   # <= 1 quantum
    assert compression_ratio({"g": g}) < 0.27


def test_compressed_psum_single_axis():
    from repro.distributed.compression import compressed_psum_mean
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()
    g = {"w": jnp.linspace(-1, 1, 512).reshape(2, 256)}
    out = compressed_psum_mean(g, mesh, axis="data")
    np.testing.assert_allclose(out["w"], g["w"], atol=2e-2)


# ---------------------------------------------------------------------------
# roofline math
# ---------------------------------------------------------------------------

def test_roofline_terms():
    r = Roofline("a", "s", "16x16", 256, flops_per_device=197e12,
                 bytes_per_device=819e9, collective_bytes_per_device=50e9,
                 collective_breakdown={}, model_flops_total=197e12 * 256,
                 peak_memory_per_device=0)
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(1.0)
    assert r.t_collective == pytest.approx(1.0)
    assert r.useful_flops_ratio == pytest.approx(1.0)
    assert r.roofline_fraction == pytest.approx(1.0)
    r2 = Roofline("a", "s", "16x16", 256, 1e12, 900e9, 1e9, {}, 1e12 * 256,
                  0)
    assert r2.dominant == "memory"


def test_metrics_scrape_format():
    from repro.core.observability import Metrics
    m = Metrics()
    m.inc("requests_total", model="x")
    m.observe("latency_ms", 12.5, model="x")
    s = m.scrape()
    assert 'vsr_requests_total{model="x"} 1.0' in s
    assert 'vsr_latency_ms_count{model="x"} 1' in s
    assert m.percentile("latency_ms", 50, model="x") == 12.5


def test_metrics_histograms_are_bounded_with_exact_count_and_sum():
    from repro.core.observability import HIST_RECENT, Metrics
    m = Metrics()
    n = 100_000
    for i in range(n):
        m.observe("latency_ms", float(i), stage="x")
    h = m.hists['latency_ms{stage="x"}']
    assert len(h.recent) == HIST_RECENT < n
    assert h.count == n and h.sum == n * (n - 1) / 2
    s = m.scrape()
    assert f'vsr_latency_ms_count{{stage="x"}} {n}' in s
    assert f'vsr_latency_ms_sum{{stage="x"}} {n * (n - 1) / 2:.6f}' in s
    # percentiles read the most recent values
    assert m.percentile("latency_ms", 0, stage="x") == n - HIST_RECENT
    assert m.percentile("latency_ms", 100, stage="x") == n - 1
