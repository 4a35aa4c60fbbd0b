"""Chip benchmark of the offloader: one harness, cells defined as data.

``python chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is started
on.  Everything that belongs to one configuration, traffic mix or metric sits
in a file of its own under this directory, found by the name the benchmark
gives it.
"""
