"""The benchmark of code2vec-tpu: one command runs one cell once.

``BENCHMARK.json`` at the root of the repo names the cells; everything that
belongs to one configuration, one traffic mix, one runner or one per-layer
metric is a file of its own under this directory, found by that name
(README.md). Nothing here is imported by the program.
"""
