"""On-chip benchmark of the match stack: cells, kinds, traffic, reference,
readers.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it finds.
Every module here imports JAX inside its functions only, so collecting
the tests under ``bench/tests`` never initialises a JAX backend.
"""
