"""On-chip benchmark of the dLLM serving system.

``python3 chipbench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` serves one cell of ``BENCHMARK.json`` (a model
configuration under a traffic mix) on a TPU and prints one JSON line. Every
configuration, traffic mix, cell and metric is a file of its own under
this directory, found by the name ``BENCHMARK.json`` gives it.
"""
