"""Parity tests for the hand-tiled Pallas kernel (VERDICT r4 directive
1c): it must agree with its XLA-default formulation in interpret mode on
the CPU mesh, so the TPU path is a pure performance swap, never a
semantics change.
"""

import jax
import jax.numpy as jnp
import numpy as np

from matrixone_tpu.ops import pallas_kernels as PK


def _rand(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def test_adc_score_parity():
    G, P, M = 6, 512, 16
    key = jax.random.PRNGKey(8)
    codes = jax.random.randint(key, (G, P, M), 0, 256, jnp.int32)
    lut = _rand(9, G, M, 256)
    got = PK.adc_score_pallas(codes, lut, tile_c=256, interpret=True)
    want = jnp.sum(jnp.take_along_axis(
        lut[:, None, :, :].repeat(P, axis=1),        # [G, P, M, 256]
        codes[..., None], axis=3)[..., 0], axis=-1)  # [G, P]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_adc_score_uint8_codes():
    G, P, M = 2, 256, 8
    codes = jax.random.randint(jax.random.PRNGKey(10), (G, P, M), 0, 256,
                               jnp.int32).astype(jnp.uint8)
    lut = _rand(11, G, M, 256)
    got = PK.adc_score_pallas(codes, lut, tile_c=128, interpret=True)
    want = jnp.sum(jnp.take_along_axis(
        lut[:, None, :, :].repeat(P, axis=1),
        codes.astype(jnp.int32)[..., None], axis=3)[..., 0], axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_kernels_sql_end_to_end_lockstep(monkeypatch):
    """Whole-path lockstep on the cpu mesh: with the choice of
    `ops/kernels.py` substituted (the ADC kernel on, interpret mode) an
    IVF-PQ top-k through SQL returns what it returns without it."""
    from matrixone_tpu.frontend import Session
    from matrixone_tpu.ops import kernels as HK
    from matrixone_tpu.storage.engine import Engine
    from matrixone_tpu.utils import metrics as M
    from matrixone_tpu.vectorindex import ivf_pq

    eng = Engine()
    s = Session(catalog=eng)
    s.execute("create table v (id bigint primary key, emb vecf32(8))")
    rng = np.random.default_rng(0)
    rows = []
    for i in range(600):
        vec = "[" + ",".join(f"{v:.3f}" for v in rng.normal(size=8)) + "]"
        rows.append(f"({i}, '{vec}')")
    s.execute("insert into v values " + ",".join(rows))
    s.execute("create index iv using ivfpq on v (emb) "
              "lists = 4 op_type = 'vector_l2_ops'")
    qv = "[" + ",".join(f"{v:.3f}" for v in rng.normal(size=8)) + "]"

    def knn():
        return s.execute(f"select id from v order by"
                         f" l2_distance(emb, '{qv}') limit 5").rows()

    def traces():
        return M.pallas_traces.get(kernel="adc_score_pallas",
                                   interpret="True")

    base, before = knn(), traces()
    monkeypatch.setattr(HK, "adc_kernel_chosen",
                        lambda candidates: candidates % 128 == 0)
    # the choice is a pure function of platform and shape, so no jit
    # cache keys on it: a test that substitutes it drops the programs
    # traced under the other choice, before and after
    ivf_pq._search.clear_cache()
    try:
        assert knn() == base
    finally:
        monkeypatch.undo()
        ivf_pq._search.clear_cache()
    assert traces() > before, "the kernel was not traced"
