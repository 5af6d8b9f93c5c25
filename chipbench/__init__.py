"""Chip benchmark of the column store: one cell per (deployment, traffic).

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once on the TPU it is started on and prints
one JSON line.  Cells, deployments, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout and found here by name:
``configs/<config>.json``, ``traffic/<mix>.json``, ``metrics/<metric>.py``.
"""
