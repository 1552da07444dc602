"""The sampling epilogue as it stood before it was gated on the rows'
knobs (ISSUE 37), kept as the oracle: every row runs both masking sorts,
the softmaxes and the Gumbel draw whatever it asks for. The gated code
(``models/generation.py``, ``serving/spec_decode.py``) must give every
row these tokens and distributions bit for bit, and
``tests/test_tpu_compile.py`` compiles this form as its control.
"""
import jax
import jax.numpy as jnp

from paddle_tpu.models.generation import request_keys
from paddle_tpu.serving.spec_decode import ACCEPT_TAG, FINAL_TAG


def masked_logits(logits, temps, top_ks, top_ps):
    V = logits.shape[-1]
    logits = logits.astype(jnp.float32) / temps[:, None]
    k_eff = jnp.clip(jnp.where(top_ks > 0, top_ks, V), 1, V)
    kth = jnp.take_along_axis(jnp.sort(logits, -1)[:, ::-1],
                              (k_eff - 1)[:, None], -1)
    logits = jnp.where(logits < kth, -1e30, logits)
    sorted_l = jnp.sort(logits, -1)[:, ::-1]
    probs = jax.nn.softmax(sorted_l, -1)
    cum = jnp.cumsum(probs, -1)
    cutoff_idx = jnp.sum(cum < top_ps[:, None], -1)
    cutoff = jnp.take_along_axis(sorted_l, cutoff_idx[:, None], -1)
    apply_p = (top_ps < 1.0)[:, None]
    return jnp.where(apply_p & (logits < cutoff), -1e30, logits)


def sampling_probs(logits, temps, top_ks, top_ps):
    logits = logits.astype(jnp.float32)
    greedy = jax.nn.one_hot(jnp.argmax(logits, -1), logits.shape[-1],
                            dtype=jnp.float32)
    safe_t = jnp.where(temps > 0, temps, 1.0)
    probs = jax.nn.softmax(masked_logits(logits, safe_t, top_ks, top_ps),
                           -1)
    return jnp.where((temps > 0)[:, None], probs, greedy)


def sample_rows(logits, keys, temps, top_ks, top_ps):
    greedy = jnp.argmax(logits, -1)
    safe_t = jnp.where(temps > 0, temps, 1.0)
    masked = masked_logits(logits.astype(jnp.float32), safe_t, top_ks,
                           top_ps)
    sampled = jax.vmap(jax.random.categorical)(keys, masked)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)


def speculative_sample(target_logits, draft_tokens, draft_probs, spec_lens,
                       temps, top_ks, top_ps, base_key, seeds, sample_pos):
    R, K1, _V = target_logits.shape
    K = K1 - 1
    p = jax.vmap(lambda lg: sampling_probs(lg, temps, top_ks, top_ps),
                 in_axes=1, out_axes=1)(target_logits)
    rows = jnp.arange(R)
    if K > 0:
        p_at = jnp.take_along_axis(p[:, :K], draft_tokens[..., None],
                                   -1)[..., 0]
        q_at = jnp.take_along_axis(draft_probs, draft_tokens[..., None],
                                   -1)[..., 0]
        ratio = p_at / jnp.maximum(q_at, 1e-30)
        u = jax.vmap(
            lambda i: jax.vmap(jax.random.uniform)(
                request_keys(base_key, seeds, sample_pos + i,
                             ACCEPT_TAG)),
            out_axes=1)(jnp.arange(K))
        cand = jnp.arange(K)[None, :] < spec_lens[:, None]
        accept = (u < ratio) & cand
        n_acc = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), -1), -1)
    else:
        n_acc = jnp.zeros((R,), jnp.int32)
    rejected = n_acc < spec_lens
    p_fin = p[rows, n_acc]
    if K > 0:
        q_fin = draft_probs[rows, jnp.minimum(n_acc, K - 1)]
        res = jnp.maximum(p_fin - q_fin, 0.0)
        rs = jnp.sum(res, -1, keepdims=True)
        res = jnp.where(rs > 0, res / jnp.maximum(rs, 1e-30), p_fin)
        dist = jnp.where(rejected[:, None], res, p_fin)
    else:
        dist = p_fin
    fkeys = request_keys(base_key, seeds, sample_pos + n_acc, FINAL_TAG)
    y = jax.vmap(jax.random.categorical)(fkeys, jnp.log(dist)) \
        .astype(jnp.int32)
    if K > 0:
        padded = jnp.pad(draft_tokens, ((0, 0), (0, 1)))
        out = jnp.where(jnp.arange(K + 1)[None, :] < n_acc[:, None],
                        padded, 0)
        out = out.at[rows, n_acc].set(y)
    else:
        out = y[:, None]
    return out.astype(jnp.int32), (n_acc + 1).astype(jnp.int32)


#: knob batches of six rows over a vocabulary of 512, by name: (temps,
#: top_ks, top_ps). Row 0 is always greedy; ``ties`` is read with
#: :func:`tied_logits`
KNOBS = {
    "all_greedy": ([0, 0, 0, 0, 0, 0], [0] * 6, [1.0] * 6),
    "greedy_with_idle_masks": ([0] * 6, [0, 50, 0, 50, 0, 0],
                               [1.0, 1.0, 0.9, 0.9, 1.0, 1.0]),
    "temperature": ([0, 0.8, 0, 0.8, 1.3, 0], [0] * 6, [1.0] * 6),
    "top_k": ([0, 0.8, 0.8, 0, 0.8, 0], [0, 50, 0, 0, 50, 0], [1.0] * 6),
    "top_p": ([0, 0.8, 0.8, 0, 0.8, 0], [0] * 6,
              [1.0, 0.9, 1.0, 1.0, 0.9, 1.0]),
    "top_k_and_top_p": ([0, 0.8, 0.8, 0.8, 0.8, 0], [0, 50, 0, 50, 0, 0],
                        [1.0, 0.9, 0.9, 1.0, 1.0, 1.0]),
    "top_k_past_vocab": ([0, 0.8, 0.8, 0, 1.0, 0], [0, 512, 9999, 0, 50, 0],
                         [1.0] * 6),
    "ties": ([0, 0.8, 0.8, 0.8, 0, 0.8], [0, 4, 4, 0, 4, 2],
             [1.0, 1.0, 0.9, 0.9, 1.0, 0.5]),
}
V = 512


def knobs(name):
    temps, ks, ps = KNOBS[name]
    return (jnp.asarray(temps, jnp.float32), jnp.asarray(ks, jnp.int32),
            jnp.asarray(ps, jnp.float32))


def logits_for(name, shape, seed=0):
    """Seeded logits ``shape + (V,)``; for ``ties`` quantised so that
    every row holds many equal values, the k-th largest among them."""
    import numpy as np
    x = np.random.default_rng(seed).standard_normal(shape + (V,)) * 3.0
    if name == "ties":
        x = np.round(x)
    return jnp.asarray(x, jnp.float32)
