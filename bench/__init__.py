"""Cell benchmark of the FITing-Tree serving path on one TPU chip.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``: it makes the cell's keys,
builds the service through ``repro.index.open_pipeline``, warms it up, drives
the cell's closed-loop traffic for ``--seconds``, checks every answer against
``np.searchsorted`` on the key column, and prints one JSON result line.

Everything that belongs to one configuration, traffic mix or per-layer metric
is a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``   -- the deployment: generator, key count, error
* ``datasets/<generator>.py`` -- ``generate(n_keys, seed)``: sorted keys
* ``traffic/<mix>.json``      -- clients, request size, key distribution
* ``traffic/<distribution>.py`` -- ``make(n_keys, params)``: a key sampler
* ``metrics/<metric>.py``     -- ``read(ctx)``: one per-layer metric or None
* ``peaks.json``              -- chip peaks keyed by ``device_kind``
"""
