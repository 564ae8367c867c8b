"""Time the hand-written Pallas kernel against the XLA code beside it, on
the chip, at the shapes its production caller passes at deployment size.
Five alternating repeats a side, `block_until_ready`, compile excluded;
one JSON line a case, and the whole table under
`chiprun_out/profile_pallas.json`.  A kernel stays only where it beats
its fallback by more than the spread of the repeats, at the precision of
the fallback (each row carries both sides' error against float64 numpy).

The kernel is `adc_score_pallas`, alone and inside `ivf_pq._search` over
a 1M x 768 index of seeded random codes (`ops/kernels.py` makes the
choice; the fallback side substitutes it).

`--tiny` rehearses the same code on the CPU (interpret mode, toy shapes):
it shows that the script runs, never a time.
"""

import argparse
import json
import os
import statistics
import time

import numpy as np

REPEATS = 5


def _time_pair(jax, fallback, kernel, args, inner):
    """Five alternating repeats; seconds a call (`inner` calls a repeat,
    one wait at the end, so a program of microseconds is not timed as
    its dispatch)."""
    for fn in (fallback, kernel):
        jax.block_until_ready(fn(*args))           # compile + warm
    times = {"fallback": [], "kernel": []}
    for _ in range(REPEATS):
        for side, fn in (("fallback", fallback), ("kernel", kernel)):
            t0 = time.perf_counter()
            out = None
            for _ in range(inner):
                out = fn(*args)
            jax.block_until_ready(out)
            times[side].append((time.perf_counter() - t0) / inner)
    return times


def _row(case, shape, times, err):
    def stats(v):
        return {"repeats_s": v, "median_s": statistics.median(v),
                "spread_s": max(v) - min(v)}
    f, k = stats(times["fallback"]), stats(times["kernel"])
    wins = (k["median_s"] + max(k["spread_s"], f["spread_s"])
            < f["median_s"])
    return {"case": case, "shape": shape, "fallback_times": f,
            "kernel_times": k,
            "kernel_over_fallback": k["median_s"] / f["median_s"],
            "kernel_wins_beyond_spread": wins, **err}


def cases(jax, jnp, tiny):
    """-> (case, shape, fallback, kernel, args, inner, err thunk)"""
    from matrixone_tpu.ops import kernels as HK
    from matrixone_tpu.vectorindex import ivf_pq
    rng = np.random.default_rng(0)
    chosen = HK.adc_kernel_chosen

    def side(with_kernel, fn):
        """`fn` jitted afresh with the choice of `ops/kernels.py`
        substituted (it is read while tracing)."""
        def run(*a):
            HK.adc_kernel_chosen = lambda candidates: with_kernel
            try:
                return fn(*a)
            finally:
                HK.adc_kernel_chosen = chosen
        return jax.jit(run)

    # the candidate block ivf_pq._search builds for 1M x 768, nprobe 8:
    # 96 subspaces (indexing._pick_subspaces(768)) and build()'s own
    # default 16; one query (8 groups) and a 32-query chunk (256); lists
    # padded to 1,152 (1M rows over 1,024 lists, the longest of a
    # balanced build) and to 3,968 (max_list_factor's cap)
    shapes = [(8, 128, 4)] if tiny else [
        (8, 1152, 96), (256, 1152, 96), (8, 3968, 96), (8, 1152, 16),
        (256, 1152, 16)]
    for grp, pad, msub in shapes:
        codes = jnp.asarray(rng.integers(0, 256, (grp, pad, msub))
                            .astype(np.uint8))
        lut = jnp.asarray(rng.random((grp, msub, 256), np.float32) * 40)
        fb, kn = side(False, HK.adc_scores), side(True, HK.adc_scores)
        ref = np.take_along_axis(
            np.asarray(lut, np.float64)[:, None, :, :],
            np.asarray(codes, np.int64)[..., None], axis=3)[..., 0].sum(-1)

        def err(fb=fb, kn=kn, a=(codes, lut), ref=ref):
            return {"fallback_max_rel_err": float(np.max(
                        np.abs(np.asarray(fb(*a)) - ref) / ref)),
                    "kernel_max_rel_err": float(np.max(
                        np.abs(np.asarray(kn(*a)) - ref) / ref))}
        yield ("ops.kernels.adc_scores: adc_score_pallas against "
               "take_along_axis + sum",
               f"codes [{grp}, {pad}, {msub}] u8, lut [{grp}, {msub}, 256]",
               fb, kn, (codes, lut), 1 if grp > 8 else 10, err)

    # the calling program: one query against the whole index
    n, d, lists, msub = (4096, 32, 8, 4) if tiny else (1_000_000, 768,
                                                       1024, 96)
    counts = rng.multinomial(n, np.full(lists, 1.0 / lists))
    index = ivf_pq.IvfPqIndex(
        centroids=jnp.asarray(rng.standard_normal((lists, d), np.float32)),
        codebooks=jnp.asarray(
            rng.standard_normal((msub, 256, d // msub), np.float32)),
        codes=jnp.asarray(rng.integers(0, 256, (n, msub)).astype(np.uint8)),
        ids=jnp.arange(n, dtype=jnp.int32),
        offsets=jnp.asarray(np.concatenate([[0], np.cumsum(counts)])
                            .astype(np.int32)),
        max_cluster_size=-(-int(counts.max()) // 128) * 128, n=n)
    q = jnp.asarray(rng.standard_normal((1, d), np.float32))
    raw = ivf_pq._search.__wrapped__

    def search(index, q):
        return raw(index, q, k=60, nprobe=min(8, lists), query_chunk=1)
    fb, kn = side(False, search), side(True, search)

    def same(fb=fb, kn=kn):
        (fd, fi), (kd, ki) = fb(index, q), kn(index, q)
        return {"ids_equal": bool((np.asarray(fi) == np.asarray(ki)).all()),
                "max_rel_diff_of_scores": float(np.max(
                    np.abs(np.asarray(fd) - np.asarray(kd))
                    / np.asarray(fd)))}
    yield ("ivf_pq._search, kernel chosen against not",
           f"{n} x {d}, {lists} lists padded to {index.max_cluster_size}, "
           f"{msub} subspaces, nprobe 8, top-60, one query",
           fb, kn, (index, q), 10, same)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import matrixone_tpu  # noqa: F401  (enables x64)
    dev = jax.devices()[0]
    if not args.tiny and dev.platform != "tpu":
        raise SystemExit("needs the chip: a kernel is compiled for the "
                         "device, never interpreted (--tiny rehearses)")
    rows = []
    for case, shape, fb, kn, a, inner, err in cases(jax, jnp, args.tiny):
        try:
            row = _row(case, shape, _time_pair(jax, fb, kn, a, inner),
                       err())
        except Exception as e:       # noqa: BLE001 — a refused compile is a row
            row = {"case": case, "shape": shape, "error": repr(e)[:400]}
        row["device"] = {"platform": dev.platform, "kind": dev.device_kind}
        rows.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/profile_pallas.json", "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
