"""On-chip benchmark of the paged ISO serving engine.

One run of one cell: ``python bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.  Configurations, traffic mixes, cells and
per-layer metric readers are files under this directory, found by name
(see ``cells.py``)."""
