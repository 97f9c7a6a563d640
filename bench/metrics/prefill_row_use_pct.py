"""prefill_row_use_pct: prompt tokens prefilled (valid rows) as a share of
the rows the prefill program computed, `batch_slots x chunk` a chunk (the
registry's `prefill_tokens` over `prefill_rows`, `serve/runner.py`)."""


def read(run):
    rows = run.counters.get("prefill_rows")
    return 100.0 * run.counters["prefill_tokens"] / rows if rows else None
