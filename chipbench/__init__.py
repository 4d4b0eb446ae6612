"""The on-chip benchmark of the MeshNet segmentation service.

``python3 chipbench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once (harness.py).
A cell names a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); each metric is read by ``metrics/<name>.py``.
New cells, mixes and metrics are new files; no existing file changes.
"""
