"""The served Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled for one chip of a
described (not attached) v5e:2x2 topology, which finds what interpret mode
cannot (tiling rules, scalar stores, unsupported primitives, VMEM limits).
Widths: smollm-135m (head_dim 64 -> 2 packed words, 3 KV heads, group 3)
and granite-3-8b (head_dim 128 -> 4 words, 8 KV heads, group 4), served
with 8 slots, max_len 2048, page 16, prefill chunk 512; the paged decode
kernel also at max_len 32768, where its per-block skip list and block
tables are 16x longer, and at the benchmark cells' shapes:
granite8b.longctx_decode (granite widths, 8 slots, max_len 20480) and
phi3m.chat (phi-3-medium: head_dim 128, 10 KV heads, group 4; 16 slots,
max_len 3072).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import hamming
from repro.kernels import ops

WIDTHS = {  # arch -> (head_dim, n_kv_heads, group)
    "smollm-135m": (64, 3, 3),
    "granite-3-8b": (128, 8, 4),
}
PHI3M = (128, 10, 4)
SLOTS, MAX_LEN, PAGE, CHUNK = 8, 2048, 16, 512
LONG_LEN = 32768


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text     # the Mosaic kernel, not a fallback


def _shapes(one_chip, arch):
    dh, hk, g = WIDTHS[arch]
    w = hamming.packed_words(dh)
    nb = MAX_LEN // PAGE

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return dh, hk, g, w, nb, s


PAGED_CASES = [  # (widths, page_topn, slots, max_len), id
    *(pytest.param(WIDTHS[arch], ptn, SLOTS, max_len,
                   id=f"{arch}-{ptn_id}-{max_len}")
      for arch in WIDTHS for ptn, ptn_id in ((None, "dense"), (8, "top8"))
      for max_len in (MAX_LEN, LONG_LEN)),
    pytest.param(WIDTHS["granite-3-8b"], None, 8, 20480,
                 id="granite8b.longctx_decode"),
    pytest.param(PHI3M, None, 16, 3072, id="phi3m.chat"),
]


@pytest.mark.parametrize("widths,page_topn,slots,max_len", PAGED_CASES)
def test_paged_decode_compiles(one_chip, widths, page_topn, slots,
                               max_len):
    """Dense paged decode, and with page_topn the page-score kernel
    followed by decode over the compacted block tables."""
    dh, hk, g = widths
    w = hamming.packed_words(dh)
    nb = max_len // PAGE
    n_pages = slots * nb

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(q, kp, vp, bt, lengths, scale):
        return ops.paged_decode_attention(
            q, kp, vp, bt, d=dh, nsel=240, scale=scale, lengths=lengths,
            page_topn=page_topn, interpret=False)

    _compile(step, s((slots, hk * g, w), jnp.uint32),
             s((n_pages, hk, w, PAGE), jnp.uint32),
             s((n_pages, hk, PAGE, dh), jnp.bfloat16),
             s((slots, nb), jnp.int32), s((slots,), jnp.int32),
             s((), jnp.float32))


@pytest.mark.parametrize("arch", list(WIDTHS))
def test_decode_compiles(one_chip, arch):
    """The contiguous decode kernel, as dense (non-paged) serving calls it
    with use_kernels: bit-plane K cache, 512-token blocks."""
    dh, hk, g, w, _, s = _shapes(one_chip, arch)

    def step(q, k, v, lengths, scale):
        return ops.decode_attention(
            q, k, v, d=dh, nsel=240, scale=scale, lengths=lengths,
            block_t=512, bitplanes=True, interpret=False)

    _compile(step, s((SLOTS, hk * g, w), jnp.uint32),
             s((SLOTS, hk, w, MAX_LEN), jnp.uint32),
             s((SLOTS, hk, MAX_LEN, dh), jnp.bfloat16),
             s((SLOTS,), jnp.int32), s((), jnp.float32))


@pytest.mark.parametrize("arch", list(WIDTHS))
def test_prefill_compiles(one_chip, arch):
    dh, hk, g, w, _, s = _shapes(one_chip, arch)

    def step(q, k, v, kv_len, q_off, q_len, scale):
        return ops.prefill_attention(
            q, k, v, d=dh, nsel=240, scale=scale, kv_length=kv_len,
            q_offset=q_off, q_length=q_len, interpret=False)

    vec = s((SLOTS,), jnp.int32)
    _compile(step, s((SLOTS, hk * g, CHUNK, w), jnp.uint32),
             s((SLOTS, hk, MAX_LEN, w), jnp.uint32),
             s((SLOTS, hk, MAX_LEN, dh), jnp.bfloat16), vec, vec, vec,
             s((), jnp.float32))
