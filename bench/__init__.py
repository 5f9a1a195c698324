"""The benchmark: Hurricane ISABEL time steps through the GPULZ codec.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is
started on.  Everything a cell is made of is found by name:

- ``bench/configs/<config>.json``: the deployment (its generator and that
  generator's parameters, symbol, geometry, the guarantee and its control);
- ``bench/generators/<generator>.py``: ``time_step(cfg, seed)``, the fields;
- ``bench/traffic/<traffic>.json``: the traffic mix, naming its placement;
- ``bench/placements/<placement>.py``: ``Placement``, how a round trip is
  driven (where the field is, where the output goes);
- ``bench/controls/<kind>.py``: ``make(cfg, fields, **params)``, the
  configuration's guarantee broken on purpose;
- ``bench/workloads/<cell>.json``: the cell's correctness check;
- ``bench/metrics/<metric>.py``: ``read(ctx)``, one per-layer metric.

An end-to-end metric named ``<quantity>.<suffix>`` reports the harness's
``<quantity>`` (``compress_GBps``, ``decompress_GBps``, ``ratio``,
``setup_s``) in the cells it lists, under a bound of its own.
"""
