"""The on-chip benchmark of the OATS router (see `BENCHMARK.json`).

One command runs one cell from the root of a checkout:

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own that the harness finds by the
name in `BENCHMARK.json`:

  bench/configs/<config>.json      the deployment and the references it is checked by
  bench/traffic/<traffic>.json     the parameters of a traffic mix
  bench/workloads/<cell>.json      configuration, traffic, offered rate, batch cap
  bench/metrics/<metric>.py        one reader per per-layer metric
  bench/references/<name>.py       plain reference implementations
  bench/peaks.json                 published peaks, keyed by device kind

The general code beside them: `manifest` (loading and validation),
`loadgen` (arrivals, queries, the open-loop client), `cell` (one run of one
cell), `trace_reduce` (profiler trace -> device busy time, device time in
host spans, idle gaps), `work` (operations and bytes of a kernel call).
"""
