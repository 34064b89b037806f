"""On-chip benchmark of the federated trainer and the policy server.

Run one cell once: ``python fedbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``. The cells are listed in ``BENCHMARK.json``.
"""
