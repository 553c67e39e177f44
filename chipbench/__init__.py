"""chipbench: the repository's benchmark (BENCHMARK.json names this directory).

Everything that decides a number lives here, where a PR that claims a gain
cannot change it: traffic generation, the loop, the reduction from traces,
spans and counters to metrics, the table of peaks, the FLOP and byte
functions, each configuration's plain reference and the comparison that
decides `correct`. From the program it takes the system under test and
its spans, counters and kernel names, nothing else.

One command runs one cell:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell, a configuration, a traffic mix, a loop kind and a per-layer metric
are each a file of their own, found by the name BENCHMARK.json or the
cell's file gives (harness/catalog.py): a later PR adds files and entries
and edits nothing that is here.
"""
