"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracles,
swept across shapes/dtypes, plus engine integration (use_pallas=True)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.filter_compact import filter_mask_pallas
from repro.kernels.join_count import join_count_pallas

SENTINEL = 2**31 - 1


def _random_join_inputs(rng, n_probe, n_build, key_space, invalid_frac=0.1):
    probe = rng.integers(0, key_space, size=n_probe).astype(np.int32)
    inv = rng.random(n_probe) < invalid_frac
    probe[inv] = -1
    build = np.sort(rng.integers(0, key_space, size=n_build).astype(np.int32))
    n_pad = rng.integers(0, max(n_build // 4, 1))
    build[n_build - n_pad:] = SENTINEL
    return jnp.asarray(probe), jnp.asarray(build)


@pytest.mark.parametrize("n_probe,n_build", [
    (1, 1), (7, 13), (128, 256), (300, 1000), (1024, 64), (513, 511),
])
@pytest.mark.parametrize("key_space", [4, 1000])
def test_join_count_shapes(n_probe, n_build, key_space):
    rng = np.random.default_rng(n_probe * 31 + n_build)
    probe, build = _random_join_inputs(rng, n_probe, n_build, key_space)
    lo, cnt = join_count_pallas(probe, build, interpret=True)
    lo_ref, cnt_ref = ref.join_count_ref(probe, build)
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(lo_ref))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_ref))


@pytest.mark.parametrize("bl,bs", [(8, 16), (256, 512), (64, 1024)])
def test_join_count_block_shapes(bl, bs):
    rng = np.random.default_rng(0)
    probe, build = _random_join_inputs(rng, 500, 700, 50)
    lo, cnt = join_count_pallas(probe, build, bl=bl, bs=bs, interpret=True)
    lo_ref, cnt_ref = ref.join_count_ref(probe, build)
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(lo_ref))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_ref))


def test_join_count_all_invalid():
    probe = jnp.full((64,), -1, jnp.int32)
    build = jnp.sort(jnp.arange(32, dtype=jnp.int32))
    lo, cnt = join_count_pallas(probe, build, interpret=True)
    assert int(cnt.sum()) == 0


def test_join_count_duplicates_heavy():
    probe = jnp.asarray(np.full(200, 7, np.int32))
    build = jnp.asarray(np.sort(np.full(300, 7, np.int32)))
    lo, cnt = join_count_pallas(probe, build, interpret=True)
    assert int(lo[0]) == 0
    np.testing.assert_array_equal(np.asarray(cnt), np.full(200, 300))


@pytest.mark.parametrize("n,w", [(1, 2), (100, 3), (999, 5), (2048, 7)])
@pytest.mark.parametrize("nconds", [0, 1, 2])
def test_filter_mask_shapes(n, w, nconds):
    rng = np.random.default_rng(n * 7 + w)
    rows = rng.integers(0, 9, size=(n, w)).astype(np.int32)
    rows[rng.random(n) < 0.1] = -1  # invalid rows
    conds = tuple((int(rng.integers(0, w)), int(rng.integers(0, 9)))
                  for _ in range(nconds))
    mask, counts = filter_mask_pallas(jnp.asarray(rows), conds, interpret=True)
    mask_ref, counts_ref = ref.filter_mask_ref(jnp.asarray(rows), conds, br=512)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(mask_ref))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_ref))
    assert int(counts.sum()) == int(mask.sum())


@pytest.mark.parametrize("br", [8, 128, 512])
def test_filter_mask_block_sweep(br):
    rng = np.random.default_rng(3)
    rows = rng.integers(0, 5, size=(777, 4)).astype(np.int32)
    conds = ((1, 2), (3, 4))
    mask, counts = filter_mask_pallas(jnp.asarray(rows), conds, br=br,
                                      interpret=True)
    mask_ref, counts_ref = ref.filter_mask_ref(jnp.asarray(rows), conds, br=br)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(mask_ref))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_ref))


def test_engine_with_pallas_join_matches_oracle():
    """End-to-end: the JAX engine with use_pallas=True answers the whole
    LUBM workload identically to the oracle."""
    from repro.query import engine as E
    from repro.query import ref_engine as R
    from repro.query.dag import build_dag
    from repro.query.plan import plan_for_cq
    from repro.query.workload import WorkloadExecutor
    from repro.rdf.generator import generate, lubm_workload

    uni = generate(n_universities=1, seed=0)
    tt = E.tt_device_indexes(uni.store)
    for q in lubm_workload(uni.dictionary):
        wl = WorkloadExecutor(build_dag({q.name: plan_for_cq(q)}),
                              uni.store.stats, {}, use_pallas=True)
        out = wl.run(tt, {})[q.name]
        assert not bool(out.overflow)
        got = {tuple(r) for r in E.to_numpy(out).tolist()}
        want = R.evaluate_cq(q, uni.store).as_set()
        assert got == want, q.name


def test_property_join_count_random():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=30, deadline=None)
    @given(seed=st.integers(0, 10**6), n_probe=st.integers(1, 400),
           n_build=st.integers(1, 400), ks=st.integers(1, 30))
    def inner(seed, n_probe, n_build, ks):
        rng = np.random.default_rng(seed)
        probe, build = _random_join_inputs(rng, n_probe, n_build, ks)
        lo, cnt = join_count_pallas(probe, build, bl=64, bs=128, interpret=True)
        lo_ref, cnt_ref = ref.join_count_ref(probe, build)
        np.testing.assert_array_equal(np.asarray(lo), np.asarray(lo_ref))
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_ref))

    inner()


# ----------------------------------------------------------------------
# flash attention kernel
# ----------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,Hkv,hd", [
    (1, 32, 4, 2, 16), (2, 64, 4, 4, 32), (1, 128, 8, 2, 16),
])
@pytest.mark.parametrize("window", [0, 16])
def test_flash_attention_matches_ref(B, S, H, Hkv, hd, window):
    from repro.kernels.flash_attn import flash_attention_pallas

    rng = np.random.default_rng(B * 97 + S + window)
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)).astype(np.float32))
    got = flash_attention_pallas(q, k, v, window=window, cq=16, ck=16,
                                 interpret=True)
    want = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("cq,ck", [(8, 32), (32, 8), (64, 64)])
def test_flash_attention_block_sweep(cq, ck):
    from repro.kernels.flash_attn import flash_attention_pallas

    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 64, 4, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 16)).astype(np.float32))
    got = flash_attention_pallas(q, k, v, cq=cq, ck=ck, interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    from repro.kernels.flash_attn import flash_attention_pallas

    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.normal(size=(1, 32, 2, 16))).astype(jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 16))).astype(jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 16))).astype(jnp.bfloat16)
    got = flash_attention_pallas(q, k, v, cq=16, ck=16, interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=3e-2, atol=3e-2)
