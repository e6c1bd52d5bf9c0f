"""Run one cell of the benchmark and print its result as the last line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout; `src/` is put on the path here. The cell
is resolved by name from `BENCHMARK.json` (see `bench/manifest.py`). Only
a TPU counts: with no TPU, fewer chips than the cell asks for, a device
kind missing from `bench/peaks.json`, or no program beside the benchmark,
the run exits non-zero before any phase and prints no result.

Standard output: what the run found, line by line, then one JSON object
with `correct`, `attempted`, `failed`, `metrics` (the cell's end-to-end
metrics, or with `--trace 1` its per-layer ones), `device`, with
`--trace 1` a `breakdown`, and last `checks`: each number that decided
`correct` beside its limit. Standard error ends with the same numbers.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402  (the clock starts before anything is imported)
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def fail(msg: str, code: int) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"the program under test is not at {ROOT / 'src' / 'repro'}", 2)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import manifest

    try:
        cell = manifest.cell(manifest.load(ROOT), args.workload)
    except (manifest.ManifestError, json.JSONDecodeError) as exc:
        return fail(str(exc), 2)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"JAX reports {devices[0].platform!r}, not a TPU", 3)
    if len(devices) < cell.chips:
        return fail(f"{cell.name} needs {cell.chips} chips, JAX reports {len(devices)}", 3)
    from bench import cell as cell_mod
    from bench.work import peaks_for

    try:
        peaks = peaks_for(devices[0].device_kind)
    except KeyError as exc:
        return fail(str(exc), 3)
    out = cell_mod.run(cell, args.seed, args.seconds, bool(args.trace), T_START, peaks,
                       say=lambda m: print(m, flush=True))
    print(f"correct: {out['correct']}", file=sys.stderr)
    for name, c in out["checks"].items():
        op = ">=" if name == "compared" else "<="
        print(f"check {name}: {c['value']!r} {op} {c['limit']!r}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
