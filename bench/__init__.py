"""On-chip benchmark of the certified fast-SPSD build.

Run ``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the root of a checkout; ``BENCHMARK.json`` names the
cells.  Everything that measures (data, peaks, references, trace reduction,
required work) lives here, apart from the program under ``src/``.
"""
