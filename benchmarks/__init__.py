"""The benchmark: the yardstick later PRs are held to. See PERF.md.

Everything under this directory is the benchmark's own: traffic generation,
the reduction from traces and spans to metrics, the peaks table, the FLOP
and byte counts, the plain reference and the comparison that decides
``correct``. From the program it takes only the system under test and its
counters. Nothing here is imported by the program.
"""
