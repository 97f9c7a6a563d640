"""The program's side of a dense GQA HAD configuration: its ModelConfig and
its parameter tree, built from the benchmark's own weights.

The benchmark's weights are in the published layout (rotate-half RoPE
pairing); the program pairs dims (2i, 2i+1), so the columns of each query
and key head are permuted into that order, as a checkpoint loader would.
Hamming scores sum over dims, so the permutation leaves them unchanged.
"""
from __future__ import annotations

import dataclasses
import jax.numpy as jnp
import numpy as np


def model_config(conf: dict):
    from repro.configs import get_config
    from repro.models.config import HADConfig
    base = get_config(conf["bench"]["registry"])
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return dataclasses.replace(
        base,
        n_layers=conf["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or d // h,
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf.get("tie_word_embeddings", False)),
        layer_pattern="A", param_dtype="bfloat16",
        had=HADConfig(use_kernels=True))


def _pairing(n_heads: int, dh: int) -> np.ndarray:
    """Column order that turns rotate-half pairs into adjacent pairs."""
    j = np.arange(dh)
    within = j // 2 + (j % 2) * (dh // 2)
    return (np.arange(n_heads)[:, None] * dh + within[None]).reshape(-1)


def convert(w: dict, cfg) -> dict:
    """The program's parameter tree from the benchmark's weights
    (traceable: the harness makes and converts them in one jitted call)."""
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    vpad, tie = cfg.padded_vocab, cfg.tie_embeddings
    lw = w["layers"]
    qcols = jnp.asarray(_pairing(h, dh))
    kcols = jnp.asarray(_pairing(hk, dh))
    pad = vpad - w["embed"].shape[0]
    block = {
        "norm1": {"w": lw["norm1"]},
        "norm2": {"w": lw["norm2"]},
        "mixer": {"wq": jnp.take(lw["wq"], qcols, axis=2),
                  "wk": jnp.take(lw["wk"], kcols, axis=2),
                  "wv": lw["wv"], "wo": lw["wo"],
                  "sigma_q": lw["sigma_q"], "sigma_k": lw["sigma_k"]},
        "ffn": {"w1": lw["w1"], "w2": lw["w2"], "w3": lw["w3"]},
    }
    params = {"embed": jnp.pad(w["embed"], ((0, pad), (0, 0))),
              "final_norm": {"w": w["final_norm"]},
              "blocks": {"pos0": block}}
    if not tie:
        params["lm_head"] = jnp.pad(w["head"], ((0, 0), (0, pad)))
    return params
