"""Pallas flash attention vs oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.attention import (attention_reference,
                                     causal_block_counts, flash_attention,
                                     last_visible_block, make_flash_attention)

RNG = np.random.default_rng(2)


def _qkv(sq, sk, d):
    mk = lambda s: jnp.asarray(RNG.normal(size=s) * 0.5, jnp.float32)
    return mk((sq, d)), mk((sk, d)), mk((sk, d))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("cfg", [
    {"BLOCK_Q": 128, "BLOCK_K": 128},
    {"BLOCK_Q": 64, "BLOCK_K": 256},
])
def test_flash_matches_oracle(causal, cfg):
    q, k, v = _qkv(256, 256, 64)
    out = make_flash_attention(256, 256, 64, cfg, causal=causal,
                               interpret=True)(q, k, v)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sq,sk,bq,bk,skips", [
    (128, 512, 64, 128, False),
    (128, 512, 64, 64, True),
    (128, 512, 64, 32, True),
    (512, 128, 64, 64, True),
])
def test_prefix_cache_alignment(sq, sk, bq, bk, skips):
    """Sq < Sk: query block ends align with KV end (decode prefill).

    Sq > Sk builds and runs too; its rows that see no key are outside the
    contract (the reference gives NaN there), so only the others compare.
    """
    q, k, v = _qkv(sq, sk, 64)
    computed, total = causal_block_counts(sq, sk, bq, bk, True)
    assert (computed < total) == skips
    out = make_flash_attention(sq, sk, 64, {"BLOCK_Q": bq, "BLOCK_K": bk},
                               causal=True, interpret=True)(q, k, v)
    ref = attention_reference(q, k, v, causal=True)
    seen = slice(max(sq - sk, 0), sq)
    np.testing.assert_allclose(np.asarray(out)[seen], np.asarray(ref)[seen],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bq,bk", [(64, 64), (128, 64), (64, 256),
                                   (256, 128)])
def test_masked_blocks_neither_computed_nor_fetched(bq, bk):
    """K and V rows [384, 512) are NaN; a query block whose last visible KV
    block ends at or before row 384 never touches them, so its rows stay
    finite and match the reference on the clean data (computing a fully
    masked block would add 0 * NaN)."""
    s, poison = 512, 384
    q, k, v = _qkv(s, s, 64)
    ref = np.asarray(attention_reference(q, k, v, causal=True))
    k = k.at[poison:].set(jnp.nan)
    v = v.at[poison:].set(jnp.nan)
    out = np.asarray(make_flash_attention(
        s, s, 64, {"BLOCK_Q": bq, "BLOCK_K": bk}, causal=True,
        interpret=True)(q, k, v))
    clean = [qi for qi in range(s // bq)
             if (int(last_visible_block(qi, sq=s, sk=s, bq=bq, bk=bk)) + 1)
             * bk <= poison]
    assert clean
    rows = np.concatenate([np.arange(qi * bq, (qi + 1) * bq)
                           for qi in clean])
    assert np.isfinite(out[rows]).all()
    np.testing.assert_allclose(out[rows], ref[rows], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape,causal,counts", [
    ((4096, 4096, 512, 1024), True, (20, 32)),
    ((4096, 4096, 256, 512), True, (72, 128)),
    ((4096, 4096, 256, 512), False, (128, 128)),
    ((128, 512, 64, 128), True, (8, 8)),
    ((128, 512, 64, 64), False, (16, 16)),
])
def test_causal_block_counts(shape, causal, counts):
    assert causal_block_counts(*shape, causal) == counts


def test_batched_multihead_wrapper():
    q = jnp.asarray(RNG.normal(size=(2, 4, 128, 64)) * 0.5, jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 4, 128, 64)) * 0.5, jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 4, 128, 64)) * 0.5, jnp.float32)
    out = flash_attention(q, k, v, causal=True,
                          config={"BLOCK_Q": 64, "BLOCK_K": 64},
                          interpret=True)
    ref = jax.vmap(jax.vmap(
        lambda q, k, v: attention_reference(q, k, v, causal=True)))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@given(bq=st.sampled_from([64, 128]), bk=st.sampled_from([64, 128, 256]),
       d=st.sampled_from([64, 128]))
@settings(max_examples=8, deadline=None)
def test_property_block_sweep(bq, bk, d):
    q, k, v = _qkv(256, 256, d)
    out = make_flash_attention(256, 256, d, {"BLOCK_Q": bq, "BLOCK_K": bk},
                               causal=True, interpret=True)(q, k, v)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_invalid_blocks_rejected():
    with pytest.raises(ValueError):
        make_flash_attention(256, 256, 64, {"BLOCK_Q": 100, "BLOCK_K": 128})
