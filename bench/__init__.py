"""On-chip benchmark of the serving path: one cell (configuration x traffic
mix) per run. Entry point: ``python bench/run.py --workload <cell> --seed
<n> --seconds <s> --trace <0|1>``; cells, configurations, traffic mixes and
per-layer metrics are found by name under this directory."""
