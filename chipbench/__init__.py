"""On-chip benchmark of the Cache-Craft serving path.

Run one cell of ``BENCHMARK.json`` once, from the repository root::

    python3 -m chipbench.run --workload ds7b.rag_cold --seed 1 \
        --seconds 51 --trace 0

Everything a cell needs is found by name: the model configuration in
``chipbench/configs/<config>.json``, the traffic mix in
``chipbench/traffic/<mix>.json`` and each per-layer metric's reader in
``chipbench/metrics/<metric>.py``. The traffic generator, the metric
arithmetic, the trace reduction, the peak table, the FLOP and byte
counts and the plain reference live here, apart from the program.
"""
