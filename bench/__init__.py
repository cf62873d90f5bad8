"""Chip benchmark of the radix-forest sampler, driven by ``BENCHMARK.json``.

One cell (a configuration under a traffic mix) runs per process:
``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``.
Everything that belongs to one configuration, traffic mix, per-layer metric
or cell lives in files of its own, found by the names in the manifest:

* ``bench/configs/<config>.json`` sizes, ``bench/configs/<config>.py`` the
  data generator made from the seed;
* ``bench/systems/<system>.py`` drives the system under test, and
  ``bench/reference/<system>.py`` is its plain reference (no ``repro``);
* ``bench/traffic/<traffic>.json`` parameters read by ``bench/traffic.py``;
* ``bench/metrics/<metric>.py`` one reader per per-layer metric;
* ``bench/limits/<cell>.json`` the limits that decide ``correct``;
* ``bench/peaks.json`` the device peaks keyed by ``device_kind``.
"""
