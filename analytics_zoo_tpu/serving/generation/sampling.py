"""Token sampling for the decode step.

Everything is per-SLOT arrays, not python scalars: sampling params ride
through the one compiled decode step as data, so a slot switching from
greedy to temperature-0.8 top-k-40 mid-stream (a new request joining)
never changes a compiled shape.

What a round's sampler RUNS is data too, chosen on the device inside
the one program from the live lanes' rows: the argmax always; the
categorical draw over `[slots, vocab]` only where some live lane has a
temperature; inside that, the descending sort over the vocabulary (the
exact k-th largest logit, `top_k` as large as the vocabulary) only
where some live lane with a temperature also set `top_k`.  A greedy
fleet pays for neither, a fleet that samples without `top_k` for no
sort, and one lane with `top_k` costs every lane of its rounds the
sort, as it always did.  The tokens are those of the unconditional
form: a round that reaches a branch runs it for all lanes with the
same key, and an unfiltered row's threshold of -inf passes what its
smallest logit passed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def sample_tokens(logits, rng, temperature, top_k, live=None):
    """Next-token ids [slots] from `logits` [slots, vocab].

    temperature [slots] float32 — <= 0 selects greedy (argmax) for that
    slot; top_k [slots] int32 — > 0 restricts sampling to the k highest
    logits for that slot, 0 disables the filter; live [slots] bool —
    the lanes whose token anyone reads (None: all of them): a dead
    lane's row keeps its last request's `temperature` and `top_k`, and
    must not keep the draw or the sort alive for the lanes beside it
    (its own token is then the argmax).  One categorical draw per slot
    from `rng`; greedy slots ignore it.  The caller splits the key
    whether or not a draw is made, so the key sequence does not depend
    on the branch taken."""
    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1)
    drawn = temperature > 0.0
    if live is not None:
        drawn = drawn & live

    def kth_largest():
        # per-slot top-k threshold: the k-th largest logit (k=0 → the
        # smallest, i.e. no filtering)
        desc = jnp.sort(logits, axis=-1)[:, ::-1]
        kk = jnp.clip(jnp.where(top_k > 0, top_k, vocab), 1, vocab) - 1
        return jnp.take_along_axis(desc, kk[:, None], axis=-1)

    def draw():
        thresh = jax.lax.cond(
            jnp.any(drawn & (top_k > 0)), kth_largest,
            lambda: jnp.full((logits.shape[0], 1), -jnp.inf, logits.dtype))
        filtered = jnp.where(logits >= thresh, logits, -jnp.inf)
        scaled = filtered / jnp.maximum(temperature, 1e-6)[:, None]
        return jax.random.categorical(rng, scaled, axis=-1)

    sampled = jax.lax.cond(jnp.any(drawn), draw, lambda: greedy)
    return jnp.where(temperature > 0.0, sampled, greedy).astype(jnp.int32)
