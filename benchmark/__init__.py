"""The benchmark: one command runs one cell of ``BENCHMARK.json`` once.

A cell is one model configuration (``configs/<name>.json``) under one
traffic mix (``traffic/<name>.json``). The harness (``harness/``) names no
cell, configuration or metric: it finds each by the name in
``BENCHMARK.json`` — the driver named in the traffic file
(``drivers/<driver>.py``), one reader per per-layer metric
(``layer_metrics/<metric>.py``), operation counts (``costs/<name>.py``), the
plain float32 reference (``reference/<name>.py``) and the device peaks
(``peaks.json``). A later PR adds files and entries; it edits none.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
