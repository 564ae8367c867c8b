"""moqa planted-bug drills — test-only reintroductions of two known
historical bug classes, used to prove the analyzer actually catches
and reduces what it claims to (tests/test_moqa.py, precheck
--qa-smoke).  Mirrors tools/mosan.plant_eviction_race.

  stale-dict-lut   the PR-7 compile-key bug: fragment programs bake
                   dictionary LOOKUP TABLES at trace time; keying the
                   compile cache on dictionary LENGTH instead of
                   CONTENT serves a stale LUT after any same-
                   cardinality string churn — plausible rows, wrong
                   strings.  Caught by the cache-stale pair.

  pad-leak         the padded-tail bug class: an aggregate kernel that
                   sums RAW data instead of masked data reads the
                   padding.  With zero padding the answer is silently
                   right; with the canary armed (utils/qa.py) the
                   poisoned tail turns the leak into a loud NaN /
                   absurd magnitude.  Caught ONLY by the canary pair —
                   the drill that justifies the canary's existence.

Both planters clear the process-global fragment compile cache on entry
AND exit: compiled-under-the-bug programs must not leak into later
(clean) runs, and clean pre-compiled programs must not mask the bug.
They also SWAP IN an isolated findings sink for the key auditor
(utils/keys.py, armed suite-wide under pytest): the auditor rightly
screams about a planted key collision, and those deliberate findings
must not leak into the session-wide zero-mismatch gate
(tests/test_mokey.py::test_suite_runs_key_audit_clean).  Callers that
want the auditor's verdict on a plant open their own nested
keys.capture() inside the plant scope.
"""

from __future__ import annotations

from contextlib import contextmanager


def _clear_fragment_cache():
    from matrixone_tpu.vm import fusion
    fusion.CACHE.clear()


@contextmanager
def plant_stale_dict_lut():
    """Key fragment programs on dictionary LENGTH only (the pre-fix
    PR-7 shape): same-cardinality content churn now serves stale LUTs."""
    from matrixone_tpu.utils import keys
    from matrixone_tpu.vm import fusion

    original = fusion._dict_key

    def length_only_key(d):
        # THE PLANT: content hash dropped from the compile key
        return None if d is None else (len(d),)

    _clear_fragment_cache()
    fusion._dict_key = length_only_key
    try:
        with keys.capture():
            yield
    finally:
        fusion._dict_key = original
        _clear_fragment_cache()


@contextmanager
def plant_pad_leak():
    """Sum kernels read RAW values instead of masked values (the
    padded-tail leak class): correct with zero padding, loudly wrong
    under the armed canary."""
    import jax
    import jax.numpy as jnp
    from matrixone_tpu.ops import agg as A

    orig_seg_sum = A.seg_sum
    orig_scalar_sum = A.scalar_sum

    def leaky_seg_sum(values, gids, mask, max_groups):
        # THE PLANT: mask dropped — padding rows contribute their raw
        # buffer contents to whatever group their garbage gid lands in
        return jax.ops.segment_sum(values, gids,
                                   num_segments=max_groups)

    def leaky_scalar_sum(values, mask):
        return jnp.sum(values)

    from matrixone_tpu.utils import keys
    _clear_fragment_cache()
    A.seg_sum = leaky_seg_sum
    A.scalar_sum = leaky_scalar_sum
    try:
        with keys.capture():
            yield
    finally:
        A.seg_sum = orig_seg_sum
        A.scalar_sum = orig_scalar_sum
        _clear_fragment_cache()


_PLANTS = {"stale-dict-lut": plant_stale_dict_lut,
           "pad-leak": plant_pad_leak}


def plant(name: str):
    try:
        return _PLANTS[name]()
    except KeyError:
        raise ValueError(f"unknown plant {name!r}; use "
                         f"{sorted(_PLANTS)}")


def plant_names():
    return sorted(_PLANTS)
