"""Substrate tests: optimizer, compression, checkpoint, loop fault-tolerance."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.distributed import compression as C
from repro.optim import adam


def _params(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": {"w1": jax.random.normal(k, (8, 4)),
                  "sigma_q": jnp.asarray(1.0)},
            "b": jax.random.normal(k, (3,))}


# ---------------------------------------------------------------------------
# adam
# ---------------------------------------------------------------------------

def test_adam_reduces_quadratic_loss():
    cfg = adam.AdamWConfig(grad_clip=0.0)
    p = {"w": jnp.asarray([3.0, -2.0])}
    st = adam.init(p, cfg)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    for _ in range(200):
        g = jax.grad(loss)(p)
        p, st, _ = adam.update(g, st, p, lr=0.1, cfg=cfg)
    assert float(loss(p)) < 1e-3


def test_adam_respects_sigma_mask():
    cfg = adam.AdamWConfig()
    p = _params()
    st = adam.init(p, cfg)
    g = jax.tree.map(jnp.ones_like, p)
    p2, st2, m = adam.update(g, st, p, lr=0.1, cfg=cfg)
    # sigma buffer unchanged, weights changed
    assert float(p2["a"]["sigma_q"]) == float(p["a"]["sigma_q"])
    assert not np.allclose(np.asarray(p2["a"]["w1"]), np.asarray(p["a"]["w1"]))


def test_grad_clip_bounds_update_norm():
    cfg = adam.AdamWConfig(grad_clip=0.5)
    g = {"w": jnp.full((100,), 100.0)}
    clipped, norm = adam.clip_by_global_norm(g, 0.5)
    assert float(norm) > 0.5
    np.testing.assert_allclose(float(adam.global_norm(clipped)), 0.5, rtol=1e-5)


def test_adam_bf16_states_dtype():
    cfg = adam.AdamWConfig(state_dtype="bfloat16")
    p = _params()
    st = adam.init(p, cfg)
    assert st["mu"]["a"]["w1"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------

def test_onebit_ef_accumulates_residual():
    cfg = C.CompressionConfig(method="onebit", ef=True)
    g = {"w": jnp.asarray([1.0, -0.1, 0.5, -2.0])}
    err = C.init_error(g)
    q, err2 = C.compress_grads(g, err, cfg)
    # decompressed = scale * sign
    scale = float(jnp.mean(jnp.abs(g["w"])))
    np.testing.assert_allclose(np.abs(np.asarray(q["w"])), scale, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(err2["w"]),
                               np.asarray(g["w"] - q["w"]), rtol=1e-6)


def test_onebit_ef_converges_on_average():
    """With EF, the long-run average of transmitted grads equals the true
    gradient (residual stays bounded)."""
    cfg = C.CompressionConfig(method="onebit", ef=True)
    true_g = {"w": jnp.asarray([0.3, -0.7, 0.05, 1.5])}
    err = C.init_error(true_g)
    acc = jnp.zeros(4)
    for _ in range(300):
        q, err = C.compress_grads(true_g, err, cfg)
        acc = acc + q["w"]
    np.testing.assert_allclose(np.asarray(acc / 300),
                               np.asarray(true_g["w"]), atol=0.02)


def test_int8_compression_accuracy():
    cfg = C.CompressionConfig(method="int8", ef=False)
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (1000,))}
    q, _ = C.compress_grads(g, C.init_error(g), cfg)
    err = np.abs(np.asarray(q["w"] - g["w"])).max()
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127
    assert err <= scale * 0.51


def test_psum_compressed_shard_map():
    """1-bit psum inside shard_map approximates the exact mean."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    devs = np.array(jax.devices()[:1])
    mesh = Mesh(devs.reshape(1), ("dp",))
    cfg = C.CompressionConfig(method="int8")
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16))

    def f(x):
        return C.psum_compressed(x[0], "dp", cfg)[None]

    out = shard_map(f, mesh=mesh, in_specs=P("dp", None),
                    out_specs=P("dp", None))(x)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(x[0]),
                               atol=float(jnp.max(jnp.abs(x))) / 100)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    p = _params(1)
    mgr.save(10, {"params": p}, meta={"note": "x"})
    step, out = mgr.restore({"params": jax.tree.map(np.zeros_like, p)})
    assert step == 10
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), p, out["params"])


def test_checkpoint_retention_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    p = _params(2)
    for s in [1, 2, 3, 4]:
        mgr.save(s, {"params": p})
    assert mgr.steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_checkpoint_atomicity_no_tmp_left(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(5, {"params": _params(3)})
    assert not any(x.endswith(".tmp") for x in os.listdir(tmp_path))


def test_checkpoint_elastic_restore_new_sharding(tmp_path):
    """Save under one layout, restore with explicit (new) shardings —
    the elastic-rescale path."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mgr = CheckpointManager(str(tmp_path))
    p = {"w": jnp.arange(16.0).reshape(4, 4)}
    mgr.save(1, {"params": p})
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    sh = {"params": {"w": NamedSharding(mesh, P("data", None))}}
    _, out = mgr.restore({"params": jax.tree.map(np.zeros_like, p)},
                         shardings=sh)
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                  np.asarray(p["w"]))
    assert out["params"]["w"].sharding == sh["params"]["w"]
