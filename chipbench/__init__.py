"""The chip benchmark: one cell (a model configuration under a traffic mix)
per run, driven by ``BENCHMARK.json`` and the data files beside it.
Run it as ``python3 chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``."""
