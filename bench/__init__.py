"""On-chip benchmark of the DRIM fleet simulator and drim-bnn serving.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

`BENCHMARK.json` at the checkout's root lists the cells and metrics.
Everything that belongs to one configuration, traffic mix, entry kind or
metric lives in a file of its own, found by its name:

  bench/configs/<config>.json    sizes, guarantees, plain reference module
  bench/traffic/<traffic>.json   traffic parameters and the entry kind
  bench/entries/<kind>.py        the caller of one entry point
  bench/metrics/<metric>.py      the reader of one metric
  bench/reference/<module>.py    plain references, independent of src/
  bench/peaks.json               peak rates by JAX `device_kind`

The program under test is imported from `src/`; the benchmark takes from
it only the system under test and the names of its programs and kernels.
"""
