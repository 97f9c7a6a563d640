"""Serving benchmark of the HAD stack on the chip.

`python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json` and prints one JSON line. Everything that
measures or judges the program lives here: traffic generation, the plain
float32 reference, the chip peaks, the work counts, the trace reduction and
the metric readers. The program (`src/repro`) never imports this package.
"""
