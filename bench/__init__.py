"""On-chip benchmark of the RDMA data plane.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is
started on and prints one JSON result line.  Everything that measures
lives here, apart from the program under test: traffic generators
(``gen``), plain references (``reference``), trace reduction
(``harness``), the table of peaks (``peaks.json``), one file per
configuration (``configs/``), per traffic mix (``traffic/``), per cell
driver (``drivers/``) and per per-layer metric (``metrics/``).
"""
