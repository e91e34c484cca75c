"""The chip benchmark's harness: cells, traffic, clocks, traces, checks.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* ``configs/<config>.json``   the model configuration as it is run;
* ``traffic/<mix>.json``      the traffic mix's parameters, read by the one
                              generator in :mod:`chipbench.traffic`;
* ``entries/<entry>.py``      how a kind of cell drives the program
                              (``serve``, ``train``), named by the mix;
* ``metrics/<metric>.py``     one reader per metric, end to end or per
                              layer, each ``read(run) -> float | None``;
* ``costs/<op>.py``           operations and bytes of one kernel or step.

A later cell, mix or metric is a new file and a new ``BENCHMARK.json``
entry; no file here needs an edit.
"""
