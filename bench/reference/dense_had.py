"""Plain float32 reference of a dense GQA decoder with HAD attention.

Follows the published decoder (pre-norm RMSNorm, RoPE with the
rotate-half pairing of Hugging Face's Llama-family code, SwiGLU FFN, tied
or separate head) with HAD attention at inference (paper §3): queries and
keys are binarized after RoPE to sigma * sign(x) (sign(0) = +1), the score
of a key is the dot product of the two sign vectors, each query keeps its
top-N keys (every key whose score is at or above the N-th largest score
among its causal keys, ties included, N = min(topn, keys)), and a softmax
with scale sigma_q * sigma_k / sqrt(head_dim) over the kept scores weights
the float V rows. Weights are the benchmark's own (`make_weights`), in the
published layout; nothing here imports the program.

Everything is float32 with `precision=HIGHEST` matmuls. The sign-vector
scores are computed in bfloat16 with float32 accumulation, which is exact
for +-1 entries. `precision="fp8"` is the control: every matmul operand is
rounded to float8_e4m3fn under a per-tensor (weights) or per-row
(activations) scale, as an fp8 serving path would.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def spec_from_config(conf: dict, topn: int) -> dict:
    """Static sizes of the forward from a configuration file's keys."""
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return {
        "d": d, "h": h, "hk": conf["num_key_value_heads"],
        "dh": conf.get("head_dim") or d // h,
        "f": conf["intermediate_size"], "vocab": conf["vocab_size"],
        "layers": conf["num_hidden_layers"],
        "theta": float(conf["rope_theta"]), "eps": float(conf["rms_norm_eps"]),
        "tie": bool(conf.get("tie_word_embeddings", False)), "topn": int(topn),
    }


def _key(spec: dict) -> tuple:
    return tuple(sorted(spec.items()))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init_weights(key, spec: dict, dtype=jnp.bfloat16) -> dict:
    """Weights from a key, traceable: normal with std fan_in**-0.5 for
    projections, 0.02 for the embedding, ones for norms, sigma 1."""
    s = spec
    d, h, hk, dh, f, v, n = (s["d"], s["h"], s["hk"], s["dh"], s["f"],
                             s["vocab"], s["layers"])
    ks = jax.random.split(key, 9)
    w = {
        "embed": _normal(ks[0], (v, d), 0.02, dtype),
        "final_norm": jnp.ones((d,), dtype),
        "layers": {
            "norm1": jnp.ones((n, d), dtype),
            "norm2": jnp.ones((n, d), dtype),
            "wq": _normal(ks[1], (n, d, h * dh), d ** -0.5, dtype),
            "wk": _normal(ks[2], (n, d, hk * dh), d ** -0.5, dtype),
            "wv": _normal(ks[3], (n, d, hk * dh), d ** -0.5, dtype),
            "wo": _normal(ks[4], (n, h * dh, d), (h * dh) ** -0.5, dtype),
            "w1": _normal(ks[5], (n, d, f), d ** -0.5, dtype),
            "w3": _normal(ks[6], (n, d, f), d ** -0.5, dtype),
            "w2": _normal(ks[7], (n, f, d), f ** -0.5, dtype),
            "sigma_q": jnp.ones((n,), jnp.float32),
            "sigma_k": jnp.ones((n,), jnp.float32),
        },
    }
    if not s["tie"]:
        w["head"] = _normal(ks[8], (d, v), d ** -0.5, dtype)
    return w


def seed_key(seed: int):
    """A key from any whole seed up to 2**63 (two 32-bit halves)."""
    key = jax.random.key(seed % (2 ** 32))
    return jax.random.fold_in(key, seed // (2 ** 32))


@functools.partial(jax.jit, static_argnames=("skey", "dtype"))
def _make_weights(key, *, skey, dtype):
    return init_weights(key, dict(skey), dtype)


def make_weights(seed: int, spec: dict, dtype=jnp.bfloat16) -> dict:
    """All weights from the seed, on the device, in one jitted call."""
    return _make_weights(seed_key(seed), skey=_key(spec), dtype=dtype)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _q8(x, axis):
    """Round to float8_e4m3fn under a scale that maps the amax to 448."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(a, b, fp8: bool):
    """a [..., k] @ b [k, n] in float32, or with fp8-rounded operands."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if fp8:
        a = _q8(a, axis=-1)
        b = _q8(b, axis=None)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [T, heads, dh]; rotate-half pairing (i, i + dh/2)."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * inv[None]          # [T, dh/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos + rot * sin


def _sign(x):
    return jnp.where(x >= 0, 1.0, -1.0).astype(jnp.bfloat16)


def _attention(qb, kb, v, scale, *, s: dict, q_block: int, fp8: bool):
    """HAD attention over one sequence. qb [T, H, dh] and kb [T, Hk, dh]
    are +-1 (bf16); v [T, Hk, dh] float32. Returns [T, H, dh]."""
    t = qb.shape[0]
    hk, g, dh, n = s["hk"], s["h"] // s["hk"], s["dh"], s["topn"]
    levels = dh + 1
    iters = max(1, math.ceil(math.log2(levels)))
    kpos = jnp.arange(t)
    vt = v.transpose(1, 0, 2)                                   # [Hk, T, dh]
    if fp8:
        vt = _q8(vt, axis=None)

    def block(i):
        q = jax.lax.dynamic_slice_in_dim(qb, i * q_block, q_block, 0)
        q = q.reshape(q_block, hk, g, dh)
        # integer scores |s| <= dh are exact in bfloat16
        sc = jnp.einsum("qkgd,tkd->kgqt", q, kb,
                        preferred_element_type=jnp.bfloat16)    # [Hk,G,qb,T]
        qpos = i * q_block + jnp.arange(q_block)
        valid = kpos[None, :] <= qpos[:, None]                  # [qb, T]
        need = jnp.minimum(n, qpos + 1)[None, None, :]          # [1,1,qb]
        lo = jnp.zeros(sc.shape[:3], jnp.int32)                 # level index
        hi = jnp.full(sc.shape[:3], levels, jnp.int32)
        for _ in range(iters):
            mid = (lo + hi) // 2
            thr = (2 * mid - dh).astype(jnp.bfloat16)
            cnt = jnp.sum(jnp.logical_and(valid, sc >= thr[..., None]),
                          axis=-1)
            ok = cnt >= need
            lo = jnp.where(ok, mid, lo)
            hi = jnp.where(ok, hi, mid)
        thr = (2 * lo - dh).astype(jnp.bfloat16)
        keep = jnp.logical_and(valid, sc >= thr[..., None])
        e = jnp.where(keep, jnp.exp(scale * (sc.astype(jnp.float32) - dh)),
                      0.0)
        p = e / jnp.sum(e, axis=-1, keepdims=True)
        if fp8:
            p = _q8(p, axis=-1)
        ctx = jnp.einsum("kgqt,ktd->qkgd", p, vt, precision=HIGHEST)
        return ctx.reshape(q_block, hk * g, dh)

    out = jax.lax.map(block, jnp.arange(t // q_block))
    return out.reshape(t, hk * g, dh)


@functools.partial(jax.jit, static_argnames=("skey", "q_block", "row_block",
                                             "fp8"))
def _layer(x, lw, *, skey, q_block, row_block, fp8):
    s = dict(skey)
    t = x.shape[0]
    h, hk, dh, eps = s["h"], s["hk"], s["dh"], s["eps"]
    pos = jnp.arange(t)
    hn = _rmsnorm(x, lw["norm1"], eps)
    q = _mm(hn, lw["wq"], fp8).reshape(t, h, dh)
    k = _mm(hn, lw["wk"], fp8).reshape(t, hk, dh)
    v = _mm(hn, lw["wv"], fp8).reshape(t, hk, dh)
    q, k = _rope(q, pos, s["theta"]), _rope(k, pos, s["theta"])
    scale = lw["sigma_q"] * lw["sigma_k"] / math.sqrt(dh)
    ctx = _attention(_sign(q), _sign(k), v, scale, s=s, q_block=q_block,
                     fp8=fp8)
    x = x + _mm(ctx.reshape(t, h * dh), lw["wo"], fp8)

    def ffn(xb):
        hb = _rmsnorm(xb, lw["norm2"], eps)
        a = _mm(hb, lw["w1"], fp8)
        b = _mm(hb, lw["w3"], fp8)
        return xb + _mm(jax.nn.silu(a) * b, lw["w2"], fp8)

    xs = x.reshape(t // row_block, row_block, -1)
    return jax.lax.map(ffn, xs).reshape(t, -1)


@functools.partial(jax.jit, static_argnames=("skey", "fp8"))
def _head(x_rows, served, w, *, skey, fp8):
    """Per position: the reference's best logit, the served token's logit,
    the spread of the row, and the token the row puts first."""
    s = dict(skey)
    xn = _rmsnorm(x_rows, w["final_norm"], s["eps"])
    head = w["embed"].T if s["tie"] else w["head"]
    logits = _mm(xn, head, fp8)                                 # [P, V]
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return best, got, jnp.std(logits, axis=-1), jnp.argmax(logits, axis=-1)


def _bucket(n: int, floor: int = 256) -> int:
    return max(floor, 1 << (max(n, 1) - 1).bit_length())


def hidden_rows(w: dict, spec: dict, tokens, positions, *, pad_to: int,
                q_block: int = 256, row_block: int = 512, fp8: bool = False):
    """The last layer's output at `positions` of one sequence, run padded
    to `pad_to` (padding follows every real token, so causality keeps it
    out of every real row). Rows are padded to a power of two."""
    import numpy as np
    skey = _key(spec)
    t = len(tokens)
    assert t <= pad_to and pad_to % q_block == 0 and pad_to % row_block == 0
    toks = np.zeros((pad_to,), np.int32)
    toks[:t] = tokens
    x = jnp.take(w["embed"], jnp.asarray(toks), axis=0).astype(jnp.float32)
    for i in range(spec["layers"]):
        lw = jax.tree.map(lambda a: a[i], w["layers"])
        x = _layer(x, lw, skey=skey, q_block=q_block, row_block=row_block,
                   fp8=fp8)
    pos = np.zeros((_bucket(len(positions)),), np.int32)
    pos[:len(positions)] = positions
    return jnp.take(x, jnp.asarray(pos), axis=0), len(positions)


def head_stats(w: dict, spec: dict, rows, served, *, fp8: bool = False) -> dict:
    """Per row: best (largest logit), got (logit of `served`), sd (spread
    of the row) and top (the token the row puts first), as numpy."""
    import numpy as np
    x, p = rows
    srv = np.zeros((x.shape[0],), np.int32)
    srv[:p] = served
    out = _head(x, jnp.asarray(srv), w, skey=_key(spec), fp8=fp8)
    return {k: np.asarray(v)[:p] for k, v in zip(("best", "got", "sd", "top"),
                                                  out)}
