"""Validate the loop-aware HLO cost model against analytically-known cases."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_cost as HC


def _cost(fn, *args):
    comp = jax.jit(fn).lower(*args).compile()
    return HC.module_cost(comp.as_text()), comp


def test_single_matmul_flops_exact():
    a = jnp.ones((512, 512), jnp.float32)
    c, comp = _cost(lambda a: a @ a, a)
    assert c.flops == pytest.approx(2 * 512**3, rel=1e-6)


def test_scanned_matmul_multiplied_by_trip_count():
    a = jnp.ones((256, 256), jnp.float32)

    def scanned(a):
        def body(c, _):
            return c @ a, None
        c, _ = jax.lax.scan(body, a, None, length=10)
        return c

    c, comp = _cost(scanned, a)
    assert c.flops == pytest.approx(10 * 2 * 256**3, rel=1e-6)
    # XLA's own analysis undercounts by the trip count — the bug we fix
    ca = comp.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    assert ca["flops"] < c.flops / 5


def test_nested_scan_multiplies():
    a = jnp.ones((128, 128), jnp.float32)

    def nested(a):
        def inner(c, _):
            return c @ a, None

        def outer(c, _):
            c2, _ = jax.lax.scan(inner, c, None, length=4)
            return c2, None

        c, _ = jax.lax.scan(outer, a, None, length=3)
        return c

    c, _ = _cost(nested, a)
    assert c.flops == pytest.approx(12 * 2 * 128**3, rel=1e-6)


def test_bytes_scale_with_trip_count():
    a = jnp.ones((256, 256), jnp.float32)

    def scanned(a):
        def body(c, _):
            return c @ a, None
        c, _ = jax.lax.scan(body, a, None, length=8)
        return c

    c1, _ = _cost(scanned, a)

    def once(a):
        return a @ a

    c2, _ = _cost(once, a)
    # scanned dot traffic should be ~8x the single matmul's
    assert c1.bytes == pytest.approx(8 * c2.bytes, rel=0.2)
    # and the single matmul's traffic is its operands + result
    assert c2.bytes == pytest.approx(3 * 256 * 256 * 4, rel=0.05)


def test_elementwise_assumed_fused():
    a = jnp.ones((256, 1024), jnp.float32)
    c, _ = _cost(lambda a: a * 2.0 + 1.0, a)
    assert c.bytes == 0  # fused into nothing — no unfusable ops


def test_collectives_in_loop_multiplied():
    import os
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    sh = NamedSharding(mesh, P("data"))
    x = jnp.ones((8, 64), jnp.float32)

    def fn(x):
        def body(c, _):
            s = jax.lax.with_sharding_constraint(c, sh)
            return s + jnp.sum(s), None
        c, _ = jax.lax.scan(body, x, None, length=5)
        return c

    # on 1 device no collectives appear; just check parser doesn't crash
    c, comp = _cost(fn, x)
    assert c.flops >= 0


def test_parser_on_real_hlo_text_smoke():
    """Parse a full real module (forward of a small model)."""
    from repro.models import ModelConfig
    from repro.models import model as M
    cfg = ModelConfig(name="p", family="dense", n_layers=4, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
                      head_dim=8, param_dtype="float32", q_block=16,
                      layer_pattern="AA")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.ones((2, 16), jnp.int32)}
    comp = jax.jit(
        lambda p, b: M.forward(p, b, cfg=cfg, mode="std").logits
    ).lower(params, batch).compile()
    c = HC.module_cost(comp.as_text())
    # forward flops should be at least 2 * params_in_matmuls * tokens
    from repro.models.model import param_count
    approx = 2 * (param_count(cfg) - cfg.padded_vocab * cfg.d_model) * 32
    assert c.flops > 0.5 * approx, (c.flops, approx)
    assert c.bytes > 0
