"""The chip benchmark: harness, yardstick and plain references.

Entry point: ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout. The cells,
metrics and bounds are in ``BENCHMARK.json``; each configuration,
traffic mix and per-layer metric is a file of its own, found by name:

- ``bench/configs/<config>.json`` (sizes as run) and the plain reference
  named by its ``reference`` key, beside it;
- ``bench/traffic/<traffic>.json`` (parameters read by
  ``bench/traffic_gen.py``);
- ``bench/metrics/<metric>.py`` (a ``read(run)`` function).
"""
