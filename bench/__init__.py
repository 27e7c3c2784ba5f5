"""The chip benchmark of the preemptive scheduler: RT serving tasks at
rate-monotonic priorities preempting a best-effort training job at slice
boundaries.  ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` runs one cell of ``BENCHMARK.json``."""
