"""YaRN rotary scaling on MLA's rotary dims, as DeepSeek-V3 publishes it
(rope_scaling: yarn, factor 40 over 4,096 positions, beta_fast 32,
beta_slow 1, mscale and mscale_all_dim 1)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MLAConfig, YaRNConfig
from repro.configs.deepseek_v3_671b import CONFIG as DSV3
from repro.models import layers as L

PUBLISHED = YaRNConfig(factor=40.0, original_max_position=4096,
                       beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                       mscale_all_dim=1.0)


def _hand_yarn(dim, base, y):
    """DeepSeek-V3's YarnRotaryEmbedding inverse frequencies, in numpy."""
    def corr(rot):
        return (dim * math.log(y.original_max_position / (rot * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(corr(y.beta_fast)), 0)
    high = min(math.ceil(corr(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / y.factor
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return inter * (1 - mask) + extra * mask, low, high


@pytest.mark.parametrize("dim", [8, 16, 64])
def test_yarn_frequencies_match_published_formula(dim):
    want, low, high = _hand_yarn(dim, 10_000.0, PUBLISHED)
    got = np.asarray(L.yarn_freqs(dim, 10_000.0, PUBLISHED), np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    plain = np.asarray(L.rope_freqs(dim, 10_000.0), np.float64)
    # fast dims keep their frequency, slow ones are interpolated 40-fold
    np.testing.assert_allclose(got[:low + 1], plain[:low + 1], rtol=1e-6)
    hi = int(math.ceil(high))
    np.testing.assert_allclose(got[hi:], plain[hi:] / 40.0, rtol=1e-6)


def test_softmax_scale_takes_mscale_all_dim_squared():
    m = MLAConfig(yarn=PUBLISHED)
    mscale = 0.1 * math.log(40.0) + 1.0
    assert L.mla_softmax_scale(m) == pytest.approx(mscale ** 2
                                                   / math.sqrt(192))
    assert L.mla_softmax_scale(MLAConfig()) == pytest.approx(
        1 / math.sqrt(192))
    no_all = YaRNConfig(factor=40.0, original_max_position=4096,
                        mscale_all_dim=0.0)
    assert L.mla_softmax_scale(MLAConfig(yarn=no_all)) == pytest.approx(
        1 / math.sqrt(192))


@pytest.mark.parametrize("mscale,all_dim", [(1.0, 1.0), (1.0, 0.707)])
def test_rope_amplitude_is_the_mscale_ratio(mscale, all_dim):
    y = YaRNConfig(factor=40.0, original_max_position=4096, mscale=mscale,
                   mscale_all_dim=all_dim)
    x = jax.random.normal(jax.random.key(0), (5, 2, 16), jnp.float32)
    pos = jnp.arange(5) * 997
    out = L.apply_rope(x, pos, 10_000.0, y)
    amp = (0.1 * mscale * math.log(40.0) + 1) / (
        0.1 * all_dim * math.log(40.0) + 1)
    np.testing.assert_allclose(np.linalg.norm(out, axis=-1),
                               amp * np.linalg.norm(x, axis=-1), rtol=1e-5)
    # at position 0 nothing turns
    np.testing.assert_allclose(out[0], amp * x[0], rtol=1e-6)


def test_deepseek_v3_states_published_yarn_and_smoke_keeps_it():
    assert DSV3.mla.yarn == PUBLISHED
    assert DSV3.reduced().mla.yarn == PUBLISHED
