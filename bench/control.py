"""The control of the correctness check: the step below the configuration's
precision, in the program's place.

  python3 bench/control.py --workload <cell> --seeds 11,22,33 --seconds 10

The configurations state a float32 table and float32 scores at
`Precision.HIGHEST` (and, where a fit makes the table, its products at
`HIGHEST`). The step below each that tempts a faster path is read in a
part of its own, so that each number of the check gets its own reading:

  table   the table held in bfloat16, the step below float32 (after the
          program's own lower path where it has a fit: the fit at the chip's
          default matmul precision, the configuration's `fit_precision` left
          unset), scored by the reference's product at `HIGHEST` +
          `lax.top_k` in the index's place; read by `table_off_pct`;
  routes  the same build with the float32 table, scored by the reference's
          product at `Precision.HIGH` (three bf16 passes) + `lax.top_k` in
          the index's place; read by `topk_err`.

Each seed builds the cell's deployment so, once per part, drives the cell's
own window at its own load, and prints both numbers. Each part has to come
out not correct; its readings set the upper end of each limit. The
benchmark's runs never run it.

The CPU ignores the precision flags, so there the control's routes read
nothing; `tests/test_bench_faults.py` puts bf16 products in the program's
place instead.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def device_product(precision: str):
    def product(q, t):
        import jax
        import jax.numpy as jnp

        return jnp.matmul(q, t.T, precision=getattr(jax.lax.Precision, precision))

    return product


def control_build(product, table_dtype=None):
    """A `cell.build` whose fit runs at the program's default precision, whose
    table is held in `table_dtype` (none: as built), and whose router scores
    that table with `product` in place of the index."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import cell

    def build(config, seed):
        dep = cell.build(dict(config, fit_precision=None), seed)
        if table_dtype is not None:
            dep.table = np.asarray(jnp.asarray(dep.table, table_dtype).astype(jnp.float32))
        table = jnp.asarray(dep.table)
        topk = jax.jit(lambda q, t, k: jax.lax.top_k(product(q, t), k), static_argnums=2)

        def control_topk(queries, k, candidate_mask=None):
            s, i = topk(jnp.asarray(queries), table, k)
            return np.asarray(s), np.asarray(i), dep.version

        dep.router.index.topk = control_topk
        return dep

    return build


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 3
    from bench import cell, manifest

    c = manifest.cell(manifest.load(ROOT), args.workload)
    parts = {"table": control_build(device_product("HIGHEST"), jnp.bfloat16),
             "routes": control_build(device_product("HIGH"))}
    for seed in (int(s) for s in args.seeds.split(",")):
        for part, build in parts.items():
            out = cell.run(c, seed, args.seconds, False, time.perf_counter(), None,
                           say=lambda m: print(f"  {m}", flush=True), build_fn=build)
            print(json.dumps({"control": args.workload, "part": part, "seed": seed,
                              "correct": out["correct"], "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
