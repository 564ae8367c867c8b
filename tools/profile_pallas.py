"""Profile the hand-tiled Pallas L2 kernel vs the XLA path on-chip.

MO_USE_PALLAS is opt-in and unprofiled.  Run on the chip, this prints
one JSON line with both timings so the default can be flipped to
whichever wins (recorded decision).  It needs the chip: the kernel is
compiled for the device, never interpreted.
"""

import json
import time

import jax
import jax.numpy as jnp

import matrixone_tpu  # noqa: F401
from matrixone_tpu.ops import distance
from matrixone_tpu.ops.pallas_kernels import l2_distance_sq_pallas

N, D, B = 1 << 18, 768, 256


def timeit(fn, *a, reps=5):
    out = fn(*a)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        jax.block_until_ready(fn(*a))
        best = min(best, time.time() - t0)
    return best


def main():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (N, D), jnp.float32)
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, D), jnp.float32)
    t_xla = timeit(distance.l2_distance_sq, x, q)
    t_pallas = timeit(lambda a, b: l2_distance_sq_pallas(a, b, tile_m=4096),
                      x, q)
    gflop = 2.0 * N * D * B / 1e9
    print(json.dumps({
        "metric": "pallas_vs_xla_l2",
        "backend": jax.default_backend(),
        "xla_ms": round(t_xla * 1e3, 3),
        "pallas_ms": round(t_pallas * 1e3, 3),
        "xla_gflops": round(gflop / t_xla, 1),
        "pallas_gflops": round(gflop / t_pallas, 1),
        "winner": "pallas" if t_pallas < t_xla else "xla",
    }))


if __name__ == "__main__":
    main()
