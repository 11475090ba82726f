"""On-device token sampling for the v2 serving engine.

The numpy sampler (``engine_v2.InferenceEngineV2._sample_with_logprob`` /
``process_logits``) costs one host round-trip per generated token, so
any request with temperature/top-k/top-p/logprobs/repetition-penalty was
excluded from the fused K-step decode path. This module is the same
sampler expressed as jit-friendly jax ops, batched over the ragged row
layout [S, vocab], so sampling runs inside the fused ``lax.scan`` decode
program (and, for per-token ticks, as one batched dispatch per tick).

Semantics mirror the numpy oracle EXACTLY (the oracle stays in engine_v2
as the parity reference and the fallback for host-only
``logits_processor`` callbacks):

- ``temperature <= 0``: greedy over the RAW logits; logprob from the raw
  softmax.
- ``top_k``: kth-largest VALUE threshold (``np.partition`` semantics —
  ties at the kth value survive); ``top_k <= 0`` or ``>= vocab`` disables.
- ``top_p``: nucleus over the temperature-scaled, top-k-filtered logits;
  ``cumsum(p) - p < top_p`` keep rule (the argmax always survives);
  ``top_p <= 0`` degenerates to greedy over the filtered logits;
  ``top_p >= 1`` disables.
- sampling is Gumbel-max: ``argmax(logits + G)`` — filtered ``-inf``
  entries can never win.
- the selected-token logprob is computed on the FILTERED (renormalized)
  distribution, like the oracle's ``lp_at``.
- repetition penalty is the CTRL rule over the history SET (divide
  positive logits by p, multiply negative ones), applied before
  temperature — history arrives as a boolean presence mask [S, vocab] so
  the in-scan update is one scatter per step.
- eos masking (``min_new_tokens``) sets the eos column to ``-inf`` before
  sampling, per row.

Per-sequence determinism: each row carries its own ``jax.random`` key and
every sample performs ``key, sub = split(key)`` then draws with ``sub`` —
the threefry stream is a pure function of the initial key, so the
per-token path and the fused K-step path produce bit-identical token
streams under the same seed (the parity contract the scheduler relies on
when it moves a request between paths).
"""

import functools

import jax
import jax.numpy as jnp

from .registry import registry

_NEG_INF = float("-inf")


def apply_repetition_penalty(logits, seen_mask, penalties):
    """CTRL repetition penalty, batched: where ``seen_mask`` is True,
    positive logits divide by the row's penalty and negative ones multiply
    (``process_logits`` parity). ``penalties == 1`` rows pass through
    unchanged by construction. logits [S, V] f32, seen_mask [S, V] bool,
    penalties [S] f32."""
    p = penalties[:, None]
    penalized = jnp.where(logits > 0, logits / p, logits * p)
    return jnp.where(seen_mask, penalized, logits)


def mask_eos(logits, eos_ids, block):
    """Set the eos column to -inf per row where ``block`` is True
    (min_new_tokens gating). ``eos_ids`` [S] int32 (< 0 = no eos id);
    block [S] bool."""
    cols = jnp.arange(logits.shape[-1], dtype=jnp.int32)[None, :]
    hit = (cols == eos_ids[:, None]) & block[:, None] & (eos_ids >= 0)[:, None]
    return jnp.where(hit, _NEG_INF, logits)


def filter_top_k(logits, top_ks):
    """kth-largest VALUE threshold per row (oracle ``np.partition``
    semantics: ties at the kth value are kept). ``top_ks`` [S] int32;
    ``<= 0`` or ``>= vocab`` disables the row's filter."""
    V = logits.shape[-1]
    srt = jnp.sort(logits, axis=-1)[:, ::-1]  # descending
    kk = jnp.clip(top_ks, 1, V)
    kth = jnp.take_along_axis(srt, (kk - 1)[:, None], axis=-1)  # [S, 1]
    on = ((top_ks > 0) & (top_ks < V))[:, None]
    return jnp.where(on & (logits < kth), _NEG_INF, logits)


def filter_top_p(logits, top_ps):
    """Nucleus filter per row: keep the smallest set of tokens whose
    softmax mass reaches ``top_p`` (``cumsum(p) - p < top_p`` — the
    highest-prob token always survives). Mirrors the oracle's tie order
    exactly: stable ascending argsort, reversed. ``top_ps`` [S] f32;
    rows with ``top_p <= 0`` or ``>= 1`` pass through (the degenerate
    ``top_p <= 0`` greedy case is the caller's branch, as in the
    oracle)."""
    S, V = logits.shape
    order = jnp.argsort(logits, axis=-1)[:, ::-1]  # oracle: argsort()[::-1]
    srt = jnp.take_along_axis(logits, order, axis=-1)
    p = jnp.exp(srt - srt[:, :1])  # srt[:,0] is the row max
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    keep_sorted = (jnp.cumsum(p, axis=-1) - p) < top_ps[:, None]
    rows = jnp.arange(S, dtype=jnp.int32)[:, None]
    keep = jnp.zeros((S, V), bool).at[rows, order].set(keep_sorted)
    on = ((top_ps > 0.0) & (top_ps < 1.0))[:, None]
    return jnp.where(on & ~keep, _NEG_INF, logits)


def selected_logprob(logits, toks):
    """log p(tok) under softmax(logits), per row — correct on filtered
    (-inf) logits: ``exp(-inf - m)`` is 0, so the mass renormalizes over
    the surviving set (oracle ``lp_at``)."""
    m = jnp.max(logits, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
    picked = jnp.take_along_axis(logits, toks[:, None], axis=-1)[:, 0]
    return picked - lse


def sample_core(logits, keys, temps, top_ks, top_ps, *, want_logprobs):
    """One sampling step over a batch of rows — the shared core of the
    per-token dispatch and the fused decode scan.

    logits [S, V] (any float dtype; promoted to f32), keys [S, 2] uint32
    (one legacy PRNG key per row), temps/top_ps [S] f32, top_ks [S] int32.
    Returns ``(toks [S] int32, logprobs [S] f32, new_keys [S, 2])`` —
    logprobs are zeros when ``want_logprobs`` is False (statically skips
    the extra logsumexp). Every row advances its key by exactly one
    ``split`` whether it samples or not — key-chain parity between paths
    does not depend on which rows happened to be greedy."""
    raw = logits.astype(jnp.float32)
    split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)  # [S, 2, 2]
    new_keys, subs = split[:, 0], split[:, 1]

    temps_safe = jnp.where(temps > 0, temps, 1.0)
    scaled = raw / temps_safe[:, None]
    filt = filter_top_p(filter_top_k(scaled, top_ks), top_ps)

    g = jax.vmap(
        lambda k: jax.random.gumbel(k, (raw.shape[-1],), jnp.float32))(subs)
    tok_sampled = jnp.argmax(filt + g, axis=-1).astype(jnp.int32)
    tok_greedy = jnp.argmax(raw, axis=-1).astype(jnp.int32)
    # oracle: top_p <= 0 is degenerate nucleus = greedy over the
    # scaled+top-k-filtered logits
    tok_degenerate = jnp.argmax(filt, axis=-1).astype(jnp.int32)

    greedy = temps <= 0
    degenerate = (~greedy) & (top_ps <= 0.0)
    toks = jnp.where(greedy, tok_greedy,
                     jnp.where(degenerate, tok_degenerate, tok_sampled))
    if want_logprobs:
        lp_src = jnp.where(greedy[:, None], raw, filt)
        lps = selected_logprob(lp_src, toks)
    else:
        lps = jnp.zeros(raw.shape[0], jnp.float32)
    return toks, lps, new_keys


def apply_logit_controls(logits, *, seen_mask=None, penalties=None,
                         eos_ids=None, block_eos=None):
    """Pre-sampling logit controls (``process_logits`` parity): repetition
    penalty over the history presence mask, then eos masking. Pass None to
    statically skip a control."""
    logits = logits.astype(jnp.float32)
    if seen_mask is not None:
        logits = apply_repetition_penalty(logits, seen_mask, penalties)
    if block_eos is not None:
        logits = mask_eos(logits, eos_ids, block_eos)
    return logits


@functools.partial(jax.jit, static_argnames=("want_logprobs", "use_penalty",
                                             "use_eos_mask"))
def sample_step(logits, keys, temps, top_ks, top_ps, seen_mask, penalties,
                eos_ids, block_eos, *, want_logprobs, use_penalty,
                use_eos_mask):
    """Jitted controls + sample for one batched per-token dispatch. Unused
    control operands may be passed as None (they are statically elided by
    the flags, which are part of the compile key)."""
    ctrl = apply_logit_controls(
        logits,
        seen_mask=seen_mask if use_penalty else None,
        penalties=penalties if use_penalty else None,
        eos_ids=eos_ids if use_eos_mask else None,
        block_eos=block_eos if use_eos_mask else None)
    return sample_core(ctrl, keys, temps, top_ks, top_ps,
                       want_logprobs=want_logprobs)


# ---------------------------------------------------------------------------
# Speculative decoding: on-device drafting + verification
#
# Prompt-lookup drafts are POINT MASSES (the draft "distribution" puts all
# its mass on the looked-up token), so standard speculative rejection
# sampling collapses to a target-probability coin flip: accept draft t with
# probability min(1, p_target(t) / q(t)) = p_target(t), and on the first
# rejection sample from the residual norm(max(0, p - q)) — which for a
# point mass is just p with the rejected token zeroed and renormalized.
# Greedy rows (temperature <= 0) verify by exact argmax match, reproducing
# the host ``accept_drafts`` byte-for-byte.
#
# Key discipline: each row advances its chain by exactly ONE ``split`` per
# verified window (not per token), and derives the window's d coin flips +
# one correction/bonus draw from the consumed sub-key via a fixed
# ``split(sub, d + 1)`` — the draw count is independent of the accept
# pattern and of batch composition, so the fused program and the host
# fallback (which calls the same functions row-at-a-time) produce
# bit-identical streams from the same starting key.
# ---------------------------------------------------------------------------


def ngram_draft_ring(hist, hist_len, ngrams, max_drafts, *, max_ngram, d):
    """Vectorized prompt-lookup drafting over per-row token-history ring
    buffers — the device-side ``prompt_lookup_draft``.

    ``hist`` [S, W] int32 holds the trailing W tokens of each row's
    prompt+output history with token at logical position p stored at
    ``p % W``; ``hist_len`` [S] is the logical history length. ``ngrams``
    and ``max_drafts`` are per-row (dynamic) so one compiled program
    serves mixed requests; ``max_ngram`` and ``d`` (draft width) are
    static. Returns ``(drafts [S, d] int32, dlen [S] int32)`` where
    ``dlen`` is how many leading draft entries are real (0 = no match —
    the row decodes one token this window like a plain decode).

    Match semantics mirror the host scan: find the MOST RECENT earlier
    occurrence of the trailing ``ngram`` tokens (excluding the trivial
    self-match) and draft the tokens that followed it, capped by
    ``max_drafts`` and by how many tokens actually follow the match."""
    S, W = hist.shape
    rows = jnp.arange(S, dtype=jnp.int32)[:, None]
    offs = jnp.arange(W, dtype=jnp.int32)[None, :]          # s_off: 0 = most recent
    # candidate match start (logical position): s = len - ngram - 1 - s_off
    s = hist_len[:, None] - ngrams[:, None] - 1 - offs       # [S, W]
    oldest = jnp.maximum(0, hist_len - W)                    # oldest retained pos
    valid = s >= oldest[:, None]
    jj = jnp.arange(max_ngram, dtype=jnp.int32)
    pat_pos = hist_len[:, None] - ngrams[:, None] + jj[None, :]      # [S, G]
    pat = hist[rows, pat_pos % W]                                    # [S, G]
    cand_pos = s[:, :, None] + jj[None, None, :]                     # [S, W, G]
    cand = hist[rows[:, :, None], cand_pos % W]                      # [S, W, G]
    eq = (cand == pat[:, None, :]) | (jj[None, None, :] >= ngrams[:, None, None])
    ok_row = (hist_len > ngrams) & (max_drafts > 0) & (ngrams > 0)
    match = valid & jnp.all(eq, axis=-1) & ok_row[:, None]           # [S, W]
    any_match = jnp.any(match, axis=1)
    s_off = jnp.argmax(match, axis=1).astype(jnp.int32)      # first True = most recent
    # draft tokens follow the match: logical positions (s + ngram) + j,
    # of which exactly s_off + 1 precede the end of history
    start = hist_len - 1 - s_off
    dpos = start[:, None] + jnp.arange(d, dtype=jnp.int32)[None, :]
    drafts = hist[rows, dpos % W]                                    # [S, d]
    dlen = jnp.where(any_match, jnp.minimum(max_drafts, s_off + 1), 0)
    return drafts, dlen.astype(jnp.int32)


def spec_verify_window(window_logits, drafts, dlen, keys, temps, top_ks,
                       top_ps, *, d):
    """Verify one speculative window on device and emit the accepted
    tokens plus the correction/bonus token.

    ``window_logits`` [S, 1+d, V] are the target model's next-token logits
    at the fed positions (position j conditions on the input token and
    drafts[:j]); ``drafts`` [S, d] with ``dlen`` [S] real entries; keys
    [S, 2]; temps/top_ks/top_ps as in ``sample_core``. Static ``d`` must
    match the window width.

    Returns ``(out [S, 1+d] int32, n_emit [S] int32, new_keys)``: row i
    emits ``out[i, :n_emit[i]]`` — its accepted drafts followed by one
    token sampled from the residual at the rejection position (or from
    the full distribution at position dlen when every draft was accepted
    — the "bonus" token). ``n_emit - 1`` is the accepted-draft count.
    Greedy rows use exact argmax verification and never consult the
    random draws (their streams are key-independent, like ``sample_core``)."""
    S, Np1, V = window_logits.shape
    raw = window_logits.astype(jnp.float32)
    split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    new_keys, subs = split[:, 0], split[:, 1]
    rsub = jax.vmap(lambda k: jax.random.split(k, d + 1))(subs)      # [S, d+1, 2]

    temps_safe = jnp.where(temps > 0, temps, 1.0)
    flat = raw.reshape(S * Np1, V)
    rep = lambda a: jnp.repeat(a, Np1, axis=0)
    scaled = flat / rep(temps_safe)[:, None]
    filt = filter_top_p(filter_top_k(scaled, rep(top_ks)),
                        rep(top_ps)).reshape(S, Np1, V)

    greedy = temps <= 0
    degenerate = (~greedy) & (top_ps <= 0.0)
    g_tok = jnp.argmax(raw, axis=-1).astype(jnp.int32)               # [S, 1+d]
    deg_tok = jnp.argmax(filt, axis=-1).astype(jnp.int32)

    # accept test per draft position: coin flip against the target prob of
    # the (point-mass) draft token under the filtered/scaled distribution
    lp_d = selected_logprob(filt[:, :d].reshape(S * d, V),
                            drafts.reshape(S * d)).reshape(S, d)
    u = jax.vmap(jax.vmap(lambda k: jax.random.uniform(k, ())))(rsub[:, :d])
    acc = jnp.where(greedy[:, None], drafts == g_tok[:, :d],
                    jnp.where(degenerate[:, None], drafts == deg_tok[:, :d],
                              u < jnp.exp(lp_d)))
    dj = jnp.arange(d, dtype=jnp.int32)[None, :]
    acc = acc & (dj < dlen[:, None])
    m = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1),
                axis=1).astype(jnp.int32)                    # accepted prefix length

    # correction token from position m: residual (draft token zeroed) when
    # a draft was rejected there, the full distribution otherwise (bonus)
    rows = jnp.arange(S, dtype=jnp.int32)
    logit_m_raw = raw[rows, m]
    logit_m_filt = filt[rows, m]
    rejected = m < dlen
    rej_tok = drafts[rows, jnp.minimum(m, d - 1)]
    cols = jnp.arange(V, dtype=jnp.int32)[None, :]
    resid = jnp.where(rejected[:, None] & (cols == rej_tok[:, None]),
                      _NEG_INF, logit_m_filt)
    gum = jax.vmap(lambda k: jax.random.gumbel(k, (V,), jnp.float32))(
        rsub[:, d])
    corr = jnp.where(greedy, jnp.argmax(logit_m_raw, axis=-1),
                     jnp.where(degenerate, jnp.argmax(logit_m_filt, axis=-1),
                               jnp.argmax(resid + gum, axis=-1))).astype(jnp.int32)

    jfull = jnp.arange(Np1, dtype=jnp.int32)[None, :]
    drafts_pad = jnp.concatenate([drafts, drafts[:, -1:]], axis=1)   # [S, 1+d]
    out = jnp.where(jfull < m[:, None], drafts_pad, corr[:, None])
    return out, m + 1, new_keys


def ring_append(hist, hist_len, toks, n):
    """Append ``toks[i, :n[i]]`` to row i's history ring (same layout as
    ``ngram_draft_ring``): token for logical position p lands in slot
    ``p % W``; entries past ``n`` scatter out of bounds and drop. Requires
    the append width <= W so slots within one call are distinct."""
    S, W = hist.shape
    jj = jnp.arange(toks.shape[1], dtype=jnp.int32)[None, :]
    pos = hist_len[:, None] + jj
    idx = jnp.where(jj < n[:, None], pos % W, W)             # W = OOB -> dropped
    rows = jnp.arange(S, dtype=jnp.int32)[:, None]
    return hist.at[rows, idx].set(toks, mode="drop"), hist_len + n


registry.register("sampling", "xla", True,
                  "on-device temperature/top-k/top-p sampling + logit "
                  "controls (fused-decode resident; numpy oracle retained "
                  "for logits_processor callbacks)")

registry.register("speculative", "xla", True,
                  "on-device prompt-lookup drafting (ring-buffer n-gram "
                  "match) + window verification / rejection sampling "
                  "(fused-decode resident; host prompt_lookup_draft + "
                  "accept_drafts retained as the per-token parity oracle)")
