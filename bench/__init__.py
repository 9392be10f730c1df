"""The on-chip benchmark: cells of a configuration under a traffic mix,
run by `python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`."""
