"""The benchmark: one command runs one cell once on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix, one cell
or one per-layer metric is a data file found by name (manifest.py); the
yardstick (traffic generation, metric arithmetic, trace reduction, peaks,
FLOP and byte functions, plain references, the comparison that decides
`correct`) lives here, where a PR that claims a gain cannot edit it.
"""
