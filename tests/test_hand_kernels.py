"""The kernel choice (ops/kernels.py) + narrow encodings
(ops/encodings.py).

`ops/kernels.py` chooses between XLA and a Pallas kernel from the
platform and the shape; a test stands in for the platform to see what
the choice is.  The
encodings policy must narrow dict codes losslessly, narrow ONLY f32 lanes
to bf16, and surface the resolved policy in signatures the compile keys
carry.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from matrixone_tpu.ops import encodings as ENC
from matrixone_tpu.ops import kernels as HK


@pytest.mark.parametrize("platform,candidates,kernel", [
    ("tpu", 1152, True), ("tpu", 3968, True), ("tpu", 1000, False),
    ("cpu", 1152, False)])
def test_adc_choice(monkeypatch, platform, candidates, kernel):
    monkeypatch.setattr(HK, "platform", lambda: platform)
    assert HK.adc_kernel_chosen(candidates) is kernel


def test_narrow_codes_lossless_and_width(monkeypatch):
    monkeypatch.setenv("MO_NARROW_ENCODINGS", "1")
    codes = np.arange(0, 200, dtype=np.int32)
    assert ENC.narrow_codes(codes[:100], 100).dtype == np.int8
    assert ENC.narrow_codes(codes, 200).dtype == np.int16
    assert ENC.narrow_codes(codes, 40000).dtype == np.int32
    np.testing.assert_array_equal(
        ENC.narrow_codes(codes, 200).astype(np.int32), codes)
    # never widen an already-narrow array
    a8 = codes[:100].astype(np.int8)
    assert ENC.narrow_codes(a8, 40000) is a8
    monkeypatch.setenv("MO_NARROW_ENCODINGS", "0")
    assert ENC.narrow_codes(codes, 100) is codes


def test_narrow_codes_hash_identically(monkeypatch):
    """The join/group hash must be int-width invariant, or narrow
    codes would land probe rows in the wrong bucket."""
    from matrixone_tpu.ops import hash as H
    monkeypatch.setenv("MO_NARROW_ENCODINGS", "1")
    codes = np.array([0, 1, 5, 126, 127], dtype=np.int32)
    wide = np.asarray(H.hash_column(jnp.asarray(codes)))
    slim = np.asarray(H.hash_column(
        jnp.asarray(ENC.narrow_codes(codes, 128))))
    np.testing.assert_array_equal(wide, slim)


def test_narrow_lane_f32_only(monkeypatch):
    monkeypatch.setenv("MO_NARROW_ENCODINGS", "1")
    f32 = jnp.asarray(np.array([1.1, 2.2], dtype=np.float32))
    assert ENC.narrow_lane(f32).dtype == jnp.bfloat16
    f64 = jnp.asarray(np.array([1.1], dtype=np.float64))
    assert ENC.narrow_lane(f64).dtype == f64.dtype   # double contract
    i64 = jnp.asarray(np.array([3], dtype=np.int64))
    assert ENC.narrow_lane(i64) is i64
    assert ENC.narrow_lane(None) is None
    monkeypatch.setenv("MO_NARROW_ENCODINGS", "0")
    assert ENC.narrow_lane(f32) is f32
    assert ENC.signature() == ("narrow", False)


def test_policies_ride_the_fused_compile_key(monkeypatch):
    """A flipped policy must RE-TRACE, not collide: the fragment audit
    deps carry the signature, so mokey's runtime auditor and the
    compile key see every flip."""
    from matrixone_tpu.vm import fusion as FF
    monkeypatch.setenv("MO_NARROW_ENCODINGS", "0")
    key_off = FF.ENC.signature()
    monkeypatch.setenv("MO_NARROW_ENCODINGS", "1")
    key_on = FF.ENC.signature()
    assert key_off == ("narrow", False)
    assert key_on == ("narrow", True)
    # and the fragment key/audit sites actually append it
    import inspect
    src = inspect.getsource(FF.FusedFragmentOp._runtime_key)
    assert "ENC.signature()" in src
    assert "encoding_policy" in inspect.getsource(
        FF.FusedFragmentOp._audit_deps)


def test_hand_kernels_end_to_end_sql_lockstep(monkeypatch):
    """Whole-path lockstep on the cpu mesh: the same join+group query
    answers identically with narrow dict codes forced on and off."""
    from matrixone_tpu.frontend import Session
    from matrixone_tpu.storage.engine import Engine

    def run():
        s = Session(catalog=Engine())
        try:
            s.execute("create table f (k bigint, g varchar(2),"
                      " v bigint)")
            s.execute("create table d (g varchar(2), w bigint)")
            s.execute("insert into f values " + ",".join(
                f"({i}, 'g{i % 5}', {i * 7 % 101})" for i in range(400)))
            s.execute("insert into d values " + ",".join(
                f"('g{j}', {j * 10})" for j in range(5)))
            return s.execute(
                "select f.g, count(*), sum(f.v + d.w) from f"
                " join d on f.g = d.g group by f.g"
                " order by f.g").rows()
        finally:
            s.close()

    monkeypatch.setenv("MO_PLAN_FUSION", "1")
    monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    monkeypatch.setenv("MO_NARROW_ENCODINGS", "0")
    base = run()
    monkeypatch.setenv("MO_NARROW_ENCODINGS", "1")
    assert run() == base
