"""Faults planted in the timed path, to show that `correct` catches them.

Each installs itself with a `setattr`-like function before the engine is
built: the tests pass `monkeypatch.setattr`, and `bench/run.py --fault
<name>` plain `setattr` (a run on the chip at a cell's own size). No
benchmark run plants one.

- `altered_token`: every 7th token the sampler returns is replaced by the
  next id, where it is produced.
- `unchanged_state`: a decode step returns its KV pools unchanged (its new
  key and value are never written).
- `half_batch_odd`, `half_batch_even`: the decode logits of every odd (or
  even) slot are replaced by its neighbour's, so half of the batch is left
  out and served from the other half.
"""
from __future__ import annotations


def altered_token(setattr_):
    from repro.serve import runner
    real = runner._sample_token
    calls = [0]

    def altered(logits, sp, rng):
        calls[0] += 1
        tok = real(logits, sp, rng)
        return (tok + 1) % logits.size if calls[0] % 7 == 0 else tok

    setattr_(runner, "_sample_token", altered)
    return calls


def unchanged_state(setattr_):
    from repro.models import attention_block as AB
    real = AB._update_binary_cache_paged

    def unchanged(cache, k, v, *a, **kw):
        return cache if k.shape[2] == 1 else real(cache, k, v, *a, **kw)

    setattr_(AB, "_update_binary_cache_paged", unchanged)


def _half_batch(broken: int):
    def install(setattr_):
        from repro.models import model as M
        real = M.serve_step

        def half(params, batch, caches, **kw):
            logits, caches = real(params, batch, caches, **kw)
            if batch["tokens"].shape[1] == 1:
                logits = logits.at[broken::2].set(logits[1 - broken::2])
            return logits, caches

        setattr_(M, "serve_step", half)
    return install


FAULTS = {"altered_token": altered_token, "unchanged_state": unchanged_state,
          "half_batch_odd": _half_batch(1), "half_batch_even": _half_batch(0)}
