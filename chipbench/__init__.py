"""Chip benchmark of the federated trainer: one run of one cell per process.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the root of a checkout.  Cells, configurations,
traffic mixes, limits and metrics are found by name:

- ``BENCHMARK.json`` lists the cells and the metrics;
- ``chipbench/configs/<config>.json`` holds a model's sizes;
- ``chipbench/traffic/<traffic>.json`` holds the federated job;
- ``chipbench/limits/<cell>.json`` holds the limits of the output check;
- ``chipbench/metrics/<metric>.py`` reads one metric from a run record;
- ``chipbench/kinds/<kind>.py`` holds what depends on a model kind: the
  program's model, initial weights and client data from the seed, and
  the reference's plain loss;
- ``chipbench/flops/<kind>.py`` counts a model kind's operations.
"""
