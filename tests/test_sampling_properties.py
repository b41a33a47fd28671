"""Property tests for `serving.sampling.sample`: greedy == argmax, top-k
stays inside the top k, top-p keeps at least p cumulative mass."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep: property tests skip without it
from hypothesis import given, settings, strategies as st

from repro.serving.sampling import SamplingParams, sample

SETTINGS = dict(max_examples=40, deadline=None)


def _logits(draw_vals):
    return jnp.asarray(draw_vals, jnp.float32)[None, :]   # [1, V]


logits_strategy = st.lists(
    st.floats(min_value=-10.0, max_value=10.0,
              allow_nan=False, allow_infinity=False, width=32),
    min_size=2, max_size=32)


@settings(**SETTINGS)
@given(vals=logits_strategy)
def test_greedy_is_argmax(vals):
    logits = _logits(vals)
    got = sample(logits, SamplingParams(), jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.argmax(np.asarray(logits), axis=-1))


@settings(**SETTINGS)
@given(vals=logits_strategy, k=st.integers(min_value=1, max_value=8),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_top_k_never_samples_outside_top_k(vals, k, seed):
    logits = _logits(vals)
    v = logits.shape[-1]
    k = min(k, v)
    tok = int(sample(logits, SamplingParams(temperature=1.0, top_k=k),
                     jax.random.key(seed))[0])
    kth = np.sort(np.asarray(logits)[0])[-k]
    # The sampled logit must be >= the k-th largest (ties may widen the set).
    assert np.asarray(logits)[0, tok] >= kth


@settings(**SETTINGS)
@given(vals=logits_strategy,
       p=st.floats(min_value=float(np.float32(0.05)),
                   max_value=float(np.float32(0.999)), width=32),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_top_p_keeps_cumulative_mass_at_least_p(vals, p, seed):
    """The nucleus (every token top-p can sample) must carry >= p mass, and
    the sampled token must be inside it."""
    logits = _logits(vals)
    params = SamplingParams(temperature=1.0, top_p=p)
    tok = int(sample(logits, params, jax.random.key(seed))[0])

    probs = np.asarray(jax.nn.softmax(logits, axis=-1))[0]
    order = np.argsort(-probs, kind="stable")
    cum = np.cumsum(probs[order])
    # Smallest prefix of the sorted distribution reaching p (crossing token
    # included) — the filter keeps every logit >= the prefix's smallest.
    n_keep = int(np.searchsorted(cum, p * (1 - 1e-6)) + 1)
    thresh = np.asarray(logits)[0, order[n_keep - 1]]
    kept = np.asarray(logits)[0] >= thresh
    assert float(probs[kept].sum()) >= min(p, float(cum[-1])) - 1e-5
    assert kept[tok]
