"""The chip benchmark: cells of a configuration under a traffic mix.

`python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of BENCHMARK.json on the TPU it is started on.  Everything
that defines the measurement lives here, apart from the program under test:

  configs/<name>.json       a configuration as it is run, with its source
  traffic/<mix>.json        a mix's parameters, read by `traffic.py`
  limits/<cell>.json        the limits of the comparison deciding `correct`
  metrics/<name>.py         one reader per end-to-end metric
  layer_metrics/<name>.py   one reader per per-layer metric
  references/<name>.py      the plain float32 reference of a family
  weights.py, flops.py, peaks.py, trace_reduce.py
                            weights from the seed, work counts, chip peaks,
                            and the reduction of a profiler trace
  train_cell.py, serve_cell.py
                            the drivers, chosen by the mix's `kind`
  control.py                readings of the control and the faults, run by
                            hand on the chip to set the limits
"""
