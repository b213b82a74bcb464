"""Validation of the scan-corrected HLO cost analyzer: a scanned model must
yield the same corrected flops as its unrolled twin (which XLA counts
fully), while raw cost_analysis undercounts the scan by the trip count."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_cost

L, M, K = 8, 128, 256


def _layer(p, x):
    return jnp.tanh(x @ p)


def _scan_model(ps, x):
    def body(c, p):
        return _layer(p, c), None
    y, _ = jax.lax.scan(body, x, ps)
    return y.sum()


def _loop_model(ps, x):
    for i in range(L):
        x = _layer(ps[i], x)
    return x.sum()


@pytest.fixture(scope="module")
def compiled():
    ps = jax.ShapeDtypeStruct((L, K, K), jnp.float32)
    x = jax.ShapeDtypeStruct((M, K), jnp.float32)
    return {name: jax.jit(fn).lower(ps, x).compile()
            for name, fn in (("scan", _scan_model), ("loop", _loop_model))}


def _raw_flops(c):
    ca = c.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return float(ca["flops"])


def test_raw_cost_analysis_undercounts_scan(compiled):
    """The bug this module exists for: raw flops(scan) ~ flops(loop)/L."""
    raw_scan = _raw_flops(compiled["scan"])
    raw_loop = _raw_flops(compiled["loop"])
    assert raw_scan < raw_loop / (L / 2)


def test_corrected_flops_match_unrolled(compiled):
    analytic = L * 2 * M * K * K
    got_scan = hlo_cost.analyze(compiled["scan"].as_text())["flops"]
    got_loop = hlo_cost.analyze(compiled["loop"].as_text())["flops"]
    assert got_scan == pytest.approx(analytic, rel=0.1)
    assert got_loop == pytest.approx(analytic, rel=0.1)
    assert got_scan == pytest.approx(got_loop, rel=0.1)


def test_corrected_bytes_scale_with_trip_count(compiled):
    b_scan = hlo_cost.analyze(compiled["scan"].as_text())["hbm_bytes"]
    b_loop = hlo_cost.analyze(compiled["loop"].as_text())["hbm_bytes"]
    # same order of magnitude (fusion decisions differ scan vs unrolled)
    assert b_loop / 3 <= b_scan <= b_loop * 3
    # dominated by the L weight reads + activations, not the once-counted body
    analytic_weights = L * K * K * 4
    assert b_scan > analytic_weights


def test_collectives_multiplied_by_trips():
    """An all-reduce inside a scan body must count trip-count times."""
    mesh = jax.make_mesh((1,), ("d",))
    from jax.sharding import PartitionSpec as PS

    def inner(x):
        def body(c, _):
            return jax.lax.psum(c, "d") * 0.5, None
        y, _ = jax.lax.scan(body, x, None, length=5)
        return y

    f = jax.shard_map(inner, mesh=mesh, in_specs=PS(), out_specs=PS(),
                      check_vma=False)
    c = jax.jit(f).lower(jax.ShapeDtypeStruct((64,), jnp.float32)).compile()
    out = hlo_cost.analyze(c.as_text())
    ar = out["collective_bytes_by_kind"].get("all-reduce", 0)
    assert ar == pytest.approx(5 * 64 * 4, rel=0.01), out
