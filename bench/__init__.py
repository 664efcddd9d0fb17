"""The chip benchmark of the scheduler: ``python bench/run.py --workload
<cell> --seed <n> --seconds <s> --trace <0|1>`` (see ``BENCHMARK.json``)."""
