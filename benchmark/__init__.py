"""The yardstick: BENCHMARK.json's command, its harness and its data.

Nothing here is imported by the program; from the program the benchmark
takes only the system under test (through ``builders/``). Later PRs add
files beside these and entries to BENCHMARK.json, and edit none.
"""
