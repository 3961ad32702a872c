"""On-chip benchmark of the GPTVQ quantize -> serve system.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is
started on and prints one JSON result line. Everything a cell is made of
is found by name: its configuration (``bench/configs/``), the module of
its architecture by the configuration's ``model_type`` (``bench/arch/``:
sizes, seeded payload, the program's tree over it, the plain reference and
the model's work counts), its traffic mix (``bench/traffic/``), the limits
of its correctness check (``bench/limits/``) and the reader of each
per-layer metric (``bench/metrics/``). The yardstick lives here too: the
pieces of the plain float32 references (``reference.py``), the
required-work functions of the kernels (``bench/work/``), the table of
peaks (``peaks.json``) and the trace reduction (``trace.py``).
"""
