"""On-chip benchmark of the sampled-softmax training stack.

One command runs one cell once::

  python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the checkout's root names every cell, configuration
and metric.  Each is found by name in files of its own:

    bench/configs/<config>.json     model sizes, precision, optimizer
    bench/families/<family>.py      a configuration's ``family``: its
                                    traffic generators, reference forward,
                                    head table and FLOP count
    bench/workloads/<traffic>.json  the traffic mix a general generator reads
    bench/checks/<cell>.json        the limits that decide ``correct``
    bench/metrics/<metric>.py       a per-layer metric's reader

so a cell, a configuration, a family of models or a metric is added by
adding files.  The yardstick lives here too: the traffic generators and
reference forwards of each family (``families/``), the weights
(``weights.py``), the plain float32 reference of the sampler, the loss and
the optimizer (``reference.py``), the counting rule for model FLOPs
(``flops.py``), the table of peaks (``peaks.py``) and the reduction from a
profiler trace (``tracing.py``, ``scopes.py``).  From the program under
test (``src/repro``) the benchmark takes only the train step, its
optimizer, the layout of its sampler state and of its parameters.
"""
