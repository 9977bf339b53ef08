"""Per-kernel validation: shape/dtype sweeps, allclose vs the ref.py oracles,
and host/device hash agreement. Kernels run in interpret mode on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.balancer.hashing import Hash32, fmix32 as np_fmix32
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.key_stats import key_stats
from repro.kernels.routing_lookup import routing_lookup


# ------------------------------------------------------------- key_stats --
@pytest.mark.parametrize("n,num_keys,block_n,block_k", [
    (64, 16, 32, 16),
    (1000, 257, 128, 128),
    (4096, 1024, 512, 512),
    (777, 33, 256, 64),          # ragged: padding on both axes
])
def test_key_stats_matches_oracle(n, num_keys, block_n, block_k):
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(0, num_keys, size=n), jnp.int32)
    costs = jnp.asarray(rng.uniform(0.1, 3.0, size=n), jnp.float32)
    freq, cost = key_stats(keys, costs, num_keys, block_n=block_n,
                           block_k=block_k, interpret=True)
    freq_ref, cost_ref = ref.key_stats(keys, costs, num_keys)
    np.testing.assert_allclose(freq, freq_ref, rtol=1e-6)
    np.testing.assert_allclose(cost, cost_ref, rtol=1e-5)


def test_key_stats_ignores_padding_keys():
    keys = jnp.asarray([0, 1, -1, 1, -1], jnp.int32)
    costs = jnp.ones((5,), jnp.float32)
    freq, cost = key_stats(keys, costs, 4, block_n=8, block_k=8,
                           interpret=True)
    np.testing.assert_allclose(freq, [1, 2, 0, 0])
    np.testing.assert_allclose(cost, [1, 2, 0, 0])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_key_stats_dtypes(dtype):
    rng = np.random.default_rng(1)
    keys = jnp.asarray(rng.integers(0, 100, size=500), jnp.int32)
    costs = jnp.asarray(rng.uniform(0.5, 2.0, size=500)).astype(dtype)
    freq, cost = key_stats(keys, costs, 100, interpret=True)
    freq_ref, cost_ref = ref.key_stats(keys, costs, 100)
    np.testing.assert_allclose(freq, freq_ref, rtol=1e-6)
    np.testing.assert_allclose(cost, cost_ref, rtol=2e-2)


# -------------------------------------------------------- routing_lookup --
@pytest.mark.parametrize("n,a,n_dest", [
    (100, 16, 4), (2048, 128, 16), (5000, 1000, 256), (63, 1, 2),
])
def test_routing_lookup_matches_oracle(n, a, n_dest):
    rng = np.random.default_rng(2)
    keys = jnp.asarray(rng.integers(0, 10_000, size=n), jnp.int32)
    tkeys = np.full((a,), -1, np.int32)
    tdests = np.zeros((a,), np.int32)
    n_real = max(1, a // 2)
    tkeys[:n_real] = rng.choice(10_000, size=n_real, replace=False)
    tdests[:n_real] = rng.integers(0, n_dest, size=n_real)
    out = routing_lookup(keys, jnp.asarray(tkeys), jnp.asarray(tdests),
                         n_dest, seed=7, interpret=True)
    exp = ref.routing_lookup(keys, jnp.asarray(tkeys), jnp.asarray(tdests),
                             n_dest, seed=7)
    np.testing.assert_array_equal(out, exp)


def test_routing_hash_matches_host_planner():
    """Device fmix32 == jnp oracle == numpy Hash32: the controller's plan and
    the data plane's routing agree bit-for-bit."""
    keys = np.arange(50_000, dtype=np.int64)
    host = Hash32(13, seed=5)(keys)
    empty_k = jnp.full((8,), -1, jnp.int32)
    empty_d = jnp.zeros((8,), jnp.int32)
    dev = routing_lookup(jnp.asarray(keys, jnp.int32), empty_k, empty_d, 13,
                         seed=5, interpret=True)
    oracle = ref.routing_lookup(jnp.asarray(keys, jnp.int32), empty_k,
                                empty_d, 13, seed=5)
    np.testing.assert_array_equal(np.asarray(dev), host)
    np.testing.assert_array_equal(np.asarray(oracle), host)
    # raw mix agreement too
    np.testing.assert_array_equal(
        np.asarray(ref.fmix32(jnp.asarray(keys, jnp.int32).astype(jnp.uint32), 5)),
        np_fmix32(keys.astype(np.uint32), 5))


def test_routing_table_override_wins():
    keys = jnp.asarray([3, 4, 5], jnp.int32)
    tkeys = jnp.asarray([4, -1, -1, -1], jnp.int32)
    tdests = jnp.asarray([9, 0, 0, 0], jnp.int32)
    out = routing_lookup(keys, tkeys, tdests, 10, interpret=True)
    assert int(out[1]) == 9


# ------------------------------------------------- int32 dtype contract --
# The kernels' integer lanes are 32-bit. A wider key array would be
# truncated inside the trace, so ids >= 2**31 would silently alias other
# keys — the public wrappers must REJECT wide dtypes loudly instead.

_EMPTY_K = jnp.full((8,), -1, jnp.int32)
_EMPTY_D = jnp.zeros((8,), jnp.int32)


@pytest.mark.parametrize("bad", [jnp.int64, jnp.float32, jnp.uint32])
def test_routing_lookup_rejects_non_int32_keys(bad):
    with jax.enable_x64(True):
        keys = jnp.asarray([1, 2, 3]).astype(bad)
        with pytest.raises(TypeError, match="int32 keys"):
            routing_lookup(keys, _EMPTY_K, _EMPTY_D, 4, interpret=True)


def test_routing_lookup_rejects_non_int32_table():
    keys = jnp.asarray([1, 2, 3], jnp.int32)
    with pytest.raises(TypeError, match="int32 table_keys"):
        routing_lookup(keys, _EMPTY_K.astype(jnp.float32), _EMPTY_D, 4,
                       interpret=True)
    with pytest.raises(TypeError, match="int32 table_dests"):
        routing_lookup(keys, _EMPTY_K, _EMPTY_D.astype(jnp.int16), 4,
                       interpret=True)


@pytest.mark.parametrize("bad", [jnp.int64, jnp.float32, jnp.int16])
def test_key_stats_rejects_non_int32_keys(bad):
    with jax.enable_x64(True):
        keys = jnp.asarray([0, 1, 2]).astype(bad)
        with pytest.raises(TypeError, match="int32 keys"):
            key_stats(keys, jnp.ones((3,), jnp.float32), 4, interpret=True)


def _int32_edge_keys():
    """int32 boundary ids plus keys whose fmix32 hash lands >= 2**31 —
    the mix/modulo must stay unsigned end-to-end or those wrap negative."""
    edge = np.array([0, 1, 2**31 - 2, 2**31 - 1], dtype=np.int32)
    probe = np.arange(4096, dtype=np.int64)
    high = probe[np_fmix32(probe.astype(np.uint32), 5) >= 2**31]
    assert high.size > 0                         # the regression is exercised
    return np.concatenate([edge.astype(np.int64), high[:64]])


def test_routing_boundary_keys_match_host_interpret():
    keys = _int32_edge_keys()
    host = Hash32(13, seed=5)(keys)
    dev = routing_lookup(jnp.asarray(keys, jnp.int32), _EMPTY_K, _EMPTY_D,
                         13, seed=5, interpret=True)
    np.testing.assert_array_equal(np.asarray(dev), host)


def test_key_stats_boundary_ids_interpret():
    """num_keys stays modest (dense histogram) but the VALUES flowing through
    the match matrix include int32 max ids — they must count as misses, not
    alias into the [0, num_keys) range after any internal widening."""
    keys = jnp.asarray([0, 3, 2**31 - 1, 3, 2**31 - 2], jnp.int32)
    freq, cost = key_stats(keys, jnp.ones((5,), jnp.float32), 4,
                           block_n=8, block_k=8, interpret=True)
    np.testing.assert_allclose(freq, [1, 0, 0, 2])
    np.testing.assert_allclose(cost, [1, 0, 0, 2])


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="compiled Mosaic path needs a real TPU backend")
def test_routing_boundary_keys_match_host_compiled():
    keys = _int32_edge_keys()
    host = Hash32(13, seed=5)(keys)
    dev = routing_lookup(jnp.asarray(keys, jnp.int32), _EMPTY_K, _EMPTY_D,
                         13, seed=5, interpret=False)
    np.testing.assert_array_equal(np.asarray(dev), host)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="compiled Mosaic path needs a real TPU backend")
def test_key_stats_boundary_ids_compiled():
    keys = jnp.asarray([0, 3, 2**31 - 1, 3, 2**31 - 2], jnp.int32)
    freq, cost = key_stats(keys, jnp.ones((5,), jnp.float32), 4,
                           interpret=False)
    np.testing.assert_allclose(freq[:4], [1, 0, 0, 2])
    np.testing.assert_allclose(cost[:4], [1, 0, 0, 2])


# ------------------------------------------------------- flash attention --
@pytest.mark.parametrize("b,hq,hkv,t,s,d", [
    (1, 2, 2, 64, 64, 32),        # MHA square
    (2, 8, 2, 128, 128, 64),      # GQA 4:1
    (1, 4, 1, 96, 96, 32),        # MQA, ragged T
    (1, 4, 4, 1, 256, 64),        # decode: one query vs KV cache
    (1, 8, 2, 17, 250, 32),       # chunked decode, ragged both axes
])
def test_flash_attention_matches_oracle(b, hq, hkv, t, s, d):
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((b, hq, t, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_t=64, block_s=64,
                          interpret=True)
    exp = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5)


@pytest.mark.parametrize("window", [16, 64, 300])
def test_flash_attention_sliding_window(window):
    rng = np.random.default_rng(4)
    b, hq, hkv, t, d = 1, 4, 2, 192, 32
    q = jnp.asarray(rng.standard_normal((b, hq, t, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, t, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, t, d)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window, block_t=64,
                          block_s=64, interpret=True)
    exp = ref.flash_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
def test_flash_attention_dtypes(dtype, atol):
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((1, 4, 128, 64))).astype(dtype)
    k = jnp.asarray(rng.standard_normal((1, 2, 128, 64))).astype(dtype)
    v = jnp.asarray(rng.standard_normal((1, 2, 128, 64))).astype(dtype)
    out = flash_attention(q, k, v, block_t=64, block_s=64, interpret=True)
    exp = ref.flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=atol)
    assert out.dtype == dtype


def test_flash_attention_matches_plain_softmax_property():
    """Row-stochastic sanity: with v = identity basis the output rows are the
    attention probabilities and must sum to 1."""
    rng = np.random.default_rng(6)
    b, h, t, d = 1, 2, 64, 64
    q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    v = jnp.broadcast_to(jnp.eye(t, d, dtype=jnp.float32), (b, h, t, d))
    out = flash_attention(q, k, v, block_t=32, block_s=32, interpret=True)
    sums = np.asarray(out).sum(-1)
    np.testing.assert_allclose(sums, np.ones_like(sums), atol=1e-5)
