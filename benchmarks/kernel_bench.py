"""Reference-path micro-benchmarks for the kernels' shapes.

These rows time the pure-jnp references (`topk_sim_ref`, `attention_ref`),
not the Pallas kernels: Pallas interpret mode is a correctness harness, not
a performance one. So every row is named `ref/...` and records the platform
it ran on; none of them is a kernel time, and a CPU row is no device number.
The derived columns carry the kernels' VMEM working set and FLOPs."""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.topk_sim.ref import topk_sim_ref
from repro.kernels.topk_sim.kernel import BLOCK_Q, BLOCK_T


def _time(fn, *args, iters=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def kernel_rows() -> List[Dict]:
    rng = np.random.default_rng(0)
    platform = jax.devices()[0].platform
    rows = []
    # topk_sim reference at both paper scales
    f = jax.jit(lambda q, t: topk_sim_ref(q, t, 5))
    for t_tools in (199, 2413):
        q = jnp.asarray(rng.normal(size=(1, 384)).astype(np.float32))
        t = jnp.asarray(rng.normal(size=(t_tools, 384)).astype(np.float32))
        us = _time(f, q, t)
        vmem_kb = (BLOCK_Q * 512 + BLOCK_T * 512 + 2 * BLOCK_Q * 32) * 4 / 1024
        rows.append({
            "name": f"ref/topk_sim_jnp/T{t_tools}",
            "us_per_call": round(us, 1),
            "derived": {"platform": platform, "tools": t_tools,
                        "kernel_vmem_kb": round(vmem_kb, 1)},
        })
    # flash attention reference at a prefill tile
    fa = jax.jit(lambda q, k, v: attention_ref(q, k, v, True, 0, 0))
    q = jnp.asarray(rng.normal(size=(8, 512, 128)).astype(np.float32))
    us = _time(fa, q, q, q, iters=3)
    rows.append({
        "name": "ref/flash_attention_jnp/bh8_s512_hd128",
        "us_per_call": round(us, 1),
        "derived": {"platform": platform, "flops": 2 * 2 * 8 * 512 * 512 * 128},
    })
    return rows
