"""heat_tpu's on-chip benchmark: `python3 -m perfbench.run --workload <cell> ...`.

Everything that belongs to one configuration, one traffic mix, one driver or
one per-layer metric is a file of its own, found by the name that
`BENCHMARK.json` gives it; `run.py` holds no table of names. `HELD.json` lists,
in the same form, cells that are built and held back (PERF.md section 7).
"""
