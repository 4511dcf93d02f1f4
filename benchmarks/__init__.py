"""The benchmark: `python -m benchmarks.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`. See benchmarks/README.md."""
