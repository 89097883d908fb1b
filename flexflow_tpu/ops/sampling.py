"""Per-slot sampling for the fixed-shape serving programs (ISSUE 14).

The serving engine runs ONE compiled slot-decode program for its whole
life; per-request sampling configs therefore cannot be trace-time
constants (a program per temperature would recompile per tenant). This
module makes sampling *data*: temperature / top-p / top-k / seed ride
the dispatch as per-slot scalar arrays — exactly like ``write_pos`` —
and every function here is shape-stable in the slot dimension, so N
tenants with N different sampling configs share one XLA program.

Counter-based RNG: a request's sample stream is a pure function of
``(seed, stream tag, draw index)`` — ``fold_in(fold_in(PRNGKey(seed),
tag), index)`` — never of the engine's key state, the slot index, or
the replica. Draw index = the position of the token being sampled
(``len(request.tokens)`` at dispatch), so a request replayed after
failover resubmission, or admitted into a different slot, reproduces
its stream bit-for-bit. Four independent streams per request:

  TAG_TARGET   — the non-speculative sampler's token draws (draw i
                 samples token i; the prefill's first token is draw 0)
  TAG_DRAFT    — the draft model's proposal draws under speculation
  TAG_ACCEPT   — the rejection-sampling accept uniforms (host rule)
  TAG_RESAMPLE — the residual re-draw after a rejection (in-graph)

Greedy is the ``temperature == 0`` degenerate case, not a separate
program: rows with temperature 0 return ``argmax(logits)`` computed
exactly as the pre-sampling greedy path did (f32 cast then argmax), so
greedy streams are bitwise-identical to a greedy-only engine.

The gate: the warp and the draw of ``sample_tokens`` and
``sampling_probs`` run under ONE ``lax.cond`` on ``any(temps > 0)``, so
a dispatch whose rows are all greedy sorts no vocabulary and draws
nothing; its other branch is that same argmax (a one-hot at it for
``sampling_probs``). A dispatch that holds one sampled row takes the
ungated computation whole, its greedy rows included. What the callers
do around these functions (the poison add, the finite check) stays
outside the gate and runs every step.

Warping semantics (shared by the sampler and ``sampling_probs`` — the
rejection-sampling accept rule depends on the two agreeing): logits are
divided by temperature and ranked in descending order, ties by
vocabulary index (a stable sort). Both filters are rank cutoffs on that
warped distribution: top-k keeps ranks ``< top_k``, top-p keeps the
ranked prefix whose exclusive cumulative probability mass is
``< top_p``. So the keep-set is "ranks ``< m``" with ``m = min(top_k or
V, nucleus prefix length)``, at least 1: the top-1 token always
survives. The sampling distribution is the softmax over the surviving
logits.

How the keep-set is computed (``_masked_warped``): nothing is ranked.
One values-only sort gives the warped values in descending order; the
nucleus prefix length is counted on them; the value at sorted position
``m - 1`` is the row's threshold. Everything strictly above the
threshold is kept, and of the entries tied AT it the first few by
vocabulary index (one scan over the tie mask), exactly the entries a
stable ranking puts below ``m``. No index array, no inverse
permutation and no vocabulary-wide gather is built.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# stream tags (fold_in domain separators): see module docstring
TAG_TARGET = 1
TAG_DRAFT = 2
TAG_ACCEPT = 3
TAG_RESAMPLE = 4


def validate_sampling(temperature, top_p, top_k, where: str = "sampling"):
    """Shared host-side validation (FFConfig, engine, router, submit):
    temperature >= 0 (0 = greedy), 0 < top_p <= 1 (1 = off),
    top_k >= 0 (0 = off)."""
    t = float(temperature)
    p = float(top_p)
    k = int(top_k)
    if not t >= 0.0:        # catches NaN too
        raise ValueError(
            f"{where}: temperature={temperature}: must be >= 0 "
            f"(0 = greedy argmax)")
    if not (0.0 < p <= 1.0):
        raise ValueError(
            f"{where}: top_p={top_p}: must be in (0, 1] "
            f"(1 = no nucleus filter)")
    if k < 0:
        raise ValueError(
            f"{where}: top_k={top_k}: must be >= 0 (0 = no top-k filter)")
    return t, p, k


def slot_keys(seeds, counters, tag: int):
    """(B,) seeds + (B,) draw indices -> (B, 2) uint32 PRNG keys on the
    ``tag`` stream. Pure per-row: row b's key depends only on
    (seeds[b], tag, counters[b])."""

    def one(s, c):
        k = jax.random.PRNGKey(s)
        k = jax.random.fold_in(k, tag)
        return jax.random.fold_in(k, c)

    return jax.vmap(one)(jnp.asarray(seeds, jnp.int32),
                         jnp.asarray(counters, jnp.int32))


def _masked_warped(logits, temps, top_ps, top_ks):
    """(B, V) f32 masked warped logits for the temperature>0 rows (rows
    with temperature 0 are resolved by the callers via argmax). The
    surviving set is ranks ``< m`` of the warped distribution, ``m`` the
    smaller of the top-k and the top-p cutoff (module docstring); rank 0
    always survives."""
    logits = logits.astype(jnp.float32)
    temps = temps.astype(jnp.float32)
    safe_t = jnp.where(temps > 0.0, temps, 1.0)[:, None]
    warped = logits / safe_t
    # the warped values in descending order: values only, and unstable,
    # because a stable sort carries an index payload to break ties and
    # equal values need no order
    sv = -jnp.sort(-warped, axis=-1, stable=False)
    # their probabilities, with softmax(warped)'s own maximum and
    # denominator (summed in vocabulary order), so sorted position j
    # holds exactly the probability of the entry a ranking puts there
    top = sv[:, :1]
    denom = jnp.sum(jnp.exp(warped - top), axis=-1, keepdims=True)
    sorted_probs = jnp.exp(sv - top) / denom
    csum = jnp.cumsum(sorted_probs, axis=-1)
    # sorted position j is in the nucleus iff the mass strictly BEFORE
    # it is < top_p: the smallest prefix reaching top_p, never empty
    in_nucleus = (csum - sorted_probs) < top_ps.astype(jnp.float32)[:, None]
    m = jnp.sum(in_nucleus, axis=-1, keepdims=True, dtype=jnp.int32)
    k = jnp.asarray(top_ks, jnp.int32)[:, None]
    m = jnp.maximum(jnp.where(k > 0, jnp.minimum(k, m), m), 1)
    thr = jnp.take_along_axis(sv, m - 1, axis=-1)        # (B, 1)
    above = warped > thr
    tied = warped == thr
    # ranks < m = everything above the threshold + the first
    # m - count(above) of the entries tied at it, by vocabulary index
    room = m - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    keep = above | (tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32)
                            <= room))
    return jnp.where(keep, warped, -jnp.inf)


def _any_sampled(temps):
    """The gate's predicate: does any row of this dispatch use the warp?
    A released slot's temperature is 0, so free slots never hold it open."""
    return jnp.any(temps > 0.0)


@jax.named_scope("sampler")
def sampling_probs(logits, temps, top_ps, top_ks):
    """The per-row sampling distribution as (B, V) f32 probabilities —
    the operand of the rejection-sampling accept rule (``p`` for the
    target, ``q`` for the draft). Rows with temperature 0 are the
    degenerate one-hot at argmax (their "distribution" is the greedy
    choice); a dispatch of such rows only takes the gate's greedy
    branch."""
    logits = logits.astype(jnp.float32)
    temps = jnp.asarray(temps, jnp.float32)

    def greedy():
        return jax.nn.one_hot(jnp.argmax(logits, axis=-1),
                              logits.shape[-1], dtype=jnp.float32)

    def warped():
        masked = _masked_warped(logits, temps, top_ps, top_ks)
        probs = jax.nn.softmax(masked, axis=-1)
        return jnp.where((temps > 0.0)[:, None], probs, greedy())

    return jax.lax.cond(_any_sampled(temps), warped, greedy)


@jax.named_scope("sampler")
def sample_tokens(logits, temps, top_ps, top_ks, seeds, counters,
                  tag: int = TAG_TARGET):
    """One token per row from the warped distribution; (B,) int32.
    temperature-0 rows take ``argmax(f32(logits))`` — bitwise the
    pre-sampling greedy decode — and a dispatch of such rows only takes
    the gate's greedy branch. Draw b is a pure function of
    (seeds[b], tag, counters[b]): slot- and replica-invariant."""
    logits = logits.astype(jnp.float32)
    temps = jnp.asarray(temps, jnp.float32)

    def greedy():
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def warped():
        masked = _masked_warped(logits, temps, top_ps, top_ks)
        keys = slot_keys(seeds, counters, tag)
        sampled = jax.vmap(
            lambda k, row: jax.random.categorical(k, row))(keys, masked)
        return jnp.where(temps > 0.0, sampled, greedy()).astype(jnp.int32)

    return jax.lax.cond(_any_sampled(temps), warped, greedy)


@jax.named_scope("sampler")
def accept_uniforms(seeds, counters, k: int):
    """(B, k) accept-rule uniforms: row b, proposal i draws from the
    ACCEPT stream at index counters[b] + i. The host compares
    ``u * q(d) <= p(d)`` — accept with probability min(1, p/q)."""
    seeds = jnp.asarray(seeds, jnp.int32)
    counters = jnp.asarray(counters, jnp.int32)

    def one(s, c):
        def per_i(i):
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(s), TAG_ACCEPT),
                c + i)
            return jax.random.uniform(key, ())

        return jax.vmap(per_i)(jnp.arange(k, dtype=jnp.int32))

    return jax.vmap(one)(seeds, counters)


@jax.named_scope("sampler")
def residual_sample(p, q, seeds, counters):
    """The in-graph rejection re-draw: sample from the residual
    distribution ``norm(max(p - q, 0))`` — what makes accept/resample
    speculation distribution-identical to sampling from ``p`` directly.
    ``p``/``q`` are (B, V) sampling distributions (the draft's q is all
    zeros for the bonus position after a fully accepted window, so the
    residual degenerates to ``p`` itself). A numerically-empty residual
    (q >= p everywhere — only reachable when p == q up to float error,
    where rejection has probability ~0) falls back to ``p``. Draws ride
    the RESAMPLE stream at the emitting token's index."""
    p = jnp.asarray(p, jnp.float32)
    q = jnp.asarray(q, jnp.float32)
    r = jnp.maximum(p - q, 0.0)
    norm = jnp.sum(r, axis=-1, keepdims=True)
    dist = jnp.where(norm > 1e-12, r / jnp.maximum(norm, 1e-12), p)
    keys = slot_keys(seeds, counters, TAG_RESAMPLE)
    logits = jnp.log(jnp.maximum(dist, 1e-38))
    return jax.vmap(
        lambda k, row: jax.random.categorical(k, row))(keys, logits
                                                       ).astype(jnp.int32)
