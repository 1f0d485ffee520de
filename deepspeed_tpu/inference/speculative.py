"""Self-speculative decoding: truncated-depth draft + one-shot verify.

One decode step per generated token leaves the chip idle between
HBM-bound cache reads. This module spends those idle FLOPs on
speculation WITHOUT a second model: the draft is the SAME network cut
short — the first ``draft_layers`` blocks of the stack, then the usual
``ln_f`` + tied head (the ``scan_layers`` PLD machinery already made
depth a scan length, so truncation is a scan-length override plus a
leading-axis slice of the stacked params/cache — no second weight set,
and the draft shares the KV cache up to its own layers).

A speculative round at row position ``p`` with pending token ``t0``:

1. **draft** (``j`` calls of one compiled program): feed ``t0`` at
   ``p``, sample ``d1``; feed ``d1`` at ``p+1``, sample ``d2``; …
   Each call runs the truncated forward and writes the DRAFT layers'
   KV at its position.
2. **verify** (ONE compiled full-depth call): teacher-force
   ``[t0, d1..dj]`` (padded to the static width ``k+1``) at positions
   ``p..p+k``. Because the draft's layer-``i`` activations (``i <
   draft_layers``) are bit-identical to the full model's on the same
   inputs, verify's full-depth KV writes subsume the draft's — the
   shared cache stays consistent by construction.
3. **accept** (in-program, no host round trip): the longest draft
   prefix that matches. Greedy: exact argmax match. Sampled: the
   standard rejection-sampling rule — accept ``d_{i+1}`` when
   ``u_i * q_i(d_{i+1}) <= p_i(d_{i+1})`` under the SAME
   temperature/top-k/top-p filters (`sampling.filtered_logits`), with
   the correction token drawn from the normalized residual
   ``max(p - q, 0)`` so outputs remain distributionally correct.
   ``m`` accepted drafts emit ``m+1`` tokens (``d1..dm`` plus the
   correction/bonus) — every round makes progress.

**Rollback never reaches a jit boundary.** Rejected-tail KV (page
slots past ``p+m``) is simply left stale: the next round REWRITES
every slot it will read before reading it (draft and verify both write
their chunk's KV ahead of attention, and the hoisted position mask
hides everything past the query position), and a row's writes past its
allocated pages land on the trash page (page 0). Host-side rollback is
an occupancy decrement with pages left allocated — pure bookkeeping.

The compile contract grows from a pinned 2 to a pinned **3** programs
— prefill, draft-step, verify-accept — held warmup-to-drain; the plain
decode program still exists but must show 0 jit-cache entries in a
speculative serve (the ``speculative`` audit rule pins exactly that).
Degenerate configs (``k == 0`` or ``draft_layers >= n_layer``) build
no decoder at all and fall back to the exact 2-program path.
"""

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.analysis.audit import donated_jit

DEFAULT_DRAFT_LAYERS = 0        # 0 = auto: n_layer // 2
DEFAULT_SPECULATIVE_K = 4


def _cfg_get(cfg, key, default):
    if cfg is None:
        return default
    if isinstance(cfg, dict):
        v = cfg.get(key, default)
    else:
        v = getattr(cfg, key, default)
    return default if v is None else v


def _slice_layers(tree, d):
    """Leading-axis prefix of every leaf of a stacked ``h`` subtree —
    the first ``d`` layers' params or cache slices."""
    return jax.tree_util.tree_map(lambda leaf: leaf[:d], tree)


def _writeback_layers(full, part):
    """Write a ``[d, ...]`` updated prefix back into the full
    ``[n_layer, ...]`` stacked tree (index-0 dynamic_update_slice —
    donation-aliasable, layers >= d flow through untouched)."""
    def upd(f, p):
        return jax.lax.dynamic_update_slice(
            f, p.astype(f.dtype), (0,) * f.ndim)
    return jax.tree_util.tree_map(upd, full, part)


def _emit_tokens(tokens, acc, corr):
    """Assemble the emitted-token block: slot ``t < acc`` carries the
    accepted draft ``d_{t+1}``, slot ``t == acc`` the correction/bonus,
    later slots are dead padding the host never reads."""
    B, k1 = tokens.shape
    pos = jnp.arange(k1)[None, :]
    shifted = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], axis=1)
    out = jnp.where(pos < acc[:, None], shifted,
                    jnp.where(pos == acc[:, None], corr[:, None], 0))
    return out.astype(jnp.int32)


def greedy_accept(pred, tokens, draft_len):
    """Greedy verify-accept. ``pred`` ``[B, k+1]`` argmax of the
    teacher-forced full-depth logits; ``tokens`` ``[B, k+1]`` =
    ``[pending, d1..dj, pad]``; ``draft_len`` ``[B]`` clamps how many
    drafts are real (padding can never be accepted). Returns
    ``(acc_len [B], out_tokens [B, k+1])`` — ``acc_len`` accepted
    drafts, so ``acc_len + 1`` tokens emit (the slot at ``acc_len`` is
    the correction, or the free bonus token when everything matched)."""
    k = tokens.shape[1] - 1
    i = jnp.arange(k)[None, :]
    ok = (i < draft_len[:, None]) & (pred[:, :-1] == tokens[:, 1:])
    acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
    corr = jnp.take_along_axis(pred, acc[:, None], axis=1)[:, 0]
    return acc, _emit_tokens(tokens, acc, corr)


def rejection_accept(probs, tokens, draft_len, q_dists, key):
    """Rejection-sampling verify-accept (Leviathan-style), vectorized —
    no scan, no host round trip.

    ``probs`` ``[B, k+1, V]``: verify (target) probabilities under the
    serving filters; ``q_dists`` ``[B, k, V]``: the draft distributions
    each ``d_{i+1}`` was actually sampled from (zeros past
    ``draft_len`` — a zero q can never win an accept test). Accept
    ``d_{i+1}`` iff ``u_i * q_i(d_{i+1}) <= p_i(d_{i+1})``; the
    correction at the first rejection samples the normalized residual
    ``max(p - q, 0)`` (falling back to ``p`` itself when the residual
    mass underflows — q == p on the whole support), and the
    all-accepted bonus slot sees q == 0, so its "residual" is exactly
    the full next-token distribution ``p_j``. Returns
    ``(acc_len, out_tokens, new_key)``."""
    B, k1, V = probs.shape
    k = k1 - 1
    key, ku, kc = jax.random.split(key, 3)
    u = jax.random.uniform(ku, (B, k), jnp.float32)
    drafts = tokens[:, 1:]
    p_d = jnp.take_along_axis(
        probs[:, :k], drafts[..., None], axis=2)[..., 0]
    q_d = jnp.take_along_axis(q_dists, drafts[..., None], axis=2)[..., 0]
    i = jnp.arange(k)[None, :]
    ok = (i < draft_len[:, None]) & (u * q_d <= p_d)
    acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
    p_m = jnp.take_along_axis(probs, acc[:, None, None], axis=1)[:, 0]
    q_pad = jnp.concatenate(
        [q_dists, jnp.zeros((B, 1, V), q_dists.dtype)], axis=1)
    q_m = jnp.take_along_axis(q_pad, acc[:, None, None], axis=1)[:, 0]
    residual = jnp.maximum(p_m - q_m, 0.0)
    mass = jnp.sum(residual, axis=-1, keepdims=True)
    corr_probs = jnp.where(mass > 1e-9, residual, p_m)
    corr = jax.random.categorical(
        kc, jnp.log(corr_probs + 1e-38), axis=-1).astype(jnp.int32)
    return acc, _emit_tokens(tokens, acc, corr), key


class SpeculativeDecoder:
    """The draft-step and verify-accept compiled programs plus their
    host bookkeeping, hung off an :class:`InferenceEngine` as
    ``engine.speculative``. Shares the engine's params, cache, PRNG
    key stream and sharding pins — it adds programs, not state."""

    def __init__(self, engine, k, draft_layers, min_accept_to_grow=0.0):
        n_layer = engine.model.config.n_layer
        if k < 1:
            raise ValueError(f"speculative k must be >= 1, got {k}")
        if not 0 < draft_layers < n_layer:
            raise ValueError(
                f"speculative draft_layers must be in 1..{n_layer - 1} "
                f"(0 < draft_layers < n_layer), got {draft_layers}")
        if k + 1 >= engine.max_seq:
            raise ValueError(
                f"speculative k={k} leaves no room in max_seq="
                f"{engine.max_seq} (need k + 1 < max_seq)")
        self.engine = engine
        self.k = int(k)
        self.draft_layers = int(draft_layers)
        self.min_accept_to_grow = float(min_accept_to_grow)
        if self.min_accept_to_grow < 0:
            raise ValueError(
                f"speculative min_accept_to_grow must be >= 0, got "
                f"{min_accept_to_grow}")
        # adaptive draft length: a host-side controller over the TRACED
        # [B] draft_len input — j is data, so varying it costs nothing.
        self._j = self.k
        self.rounds = 0
        self.row_rounds = 0             # sum of active rows over rounds
        self.accepted_total = 0         # accepted DRAFT tokens
        self.emitted_total = 0          # tokens emitted (drafts + corrections)
        self.drafted_total = 0          # draft tokens proposed
        self._draft = donated_jit(self._draft_fn, donate_argnums=(1,))
        self._verify = donated_jit(self._verify_fn, donate_argnums=(1,))

    # -- compiled programs --------------------------------------------------

    def _truncated_apply(self, params, cache, tokens, positions,
                         page_table):
        """The early-exit forward: first ``draft_layers`` blocks + ln_f
        + tied head. Under ``scan_layers`` the stacked params and cache
        leaves are sliced to ``[:d]`` (nn.scan splits params along axis
        0, so the leading axis must equal the scan length) and the
        updated cache prefix is written back in place; unrolled trees
        pass whole and merge the partial ``h_0..h_{d-1}`` updates."""
        eng = self.engine
        d = self.draft_layers
        stacked = eng.spec.stacked
        if stacked:
            params = {**params, "h": _slice_layers(params["h"], d)}
            sub = {"h": _slice_layers(cache["h"], d)}
        else:
            sub = cache
        mesh = eng.mesh if eng._cache_shardings is not None else None
        logits, new_kv = eng.model.apply(
            {"params": params}, tokens, deterministic=True,
            positions=positions, kv_cache=sub,
            attn_impl=eng.attention_impl,
            attn_block_k=eng.attention_block_k, attn_mesh=mesh,
            kv_page_table=page_table, truncate_layers=d)
        if stacked:
            cache = {**cache,
                     "h": _writeback_layers(cache["h"], new_kv["h"])}
        else:
            cache = {**cache, **new_kv}
        return logits, cache

    def _draft_fn(self, params, cache, tokens, positions, page_tables,
                  key):
        eng = self.engine
        logits, cache = self._truncated_apply(
            params, cache, tokens[:, None], positions[:, None],
            page_tables)
        logits = logits[:, 0]
        from deepspeed_tpu.inference.sampling import (
            filtered_logits,
            sample_logits,
        )
        nxt, key = sample_logits(
            logits, key, temperature=eng.temperature,
            top_k=eng.top_k, top_p=eng.top_p)
        if eng.temperature == 0.0:
            # greedy: no draft distribution to carry (accept is exact
            # match), key passes through untouched
            return nxt, key, eng._pin_cache(cache)
        q = jax.nn.softmax(
            filtered_logits(logits, eng.temperature, eng.top_k,
                            eng.top_p), axis=-1)
        return nxt, q, key, eng._pin_cache(cache)

    def _verify_fn(self, params, cache, tokens, positions, page_tables,
                   draft_len, q_dists, key):
        eng = self.engine
        mesh = eng.mesh if eng._cache_shardings is not None else None
        # always dense: the flash-decode kernel is single-query; the
        # dense path's hoisted position mask already handles T = k+1.
        logits, cache = eng.model.apply(
            {"params": params}, tokens, deterministic=True,
            positions=positions, kv_cache=cache, attn_impl="dense",
            attn_block_k=eng.attention_block_k, attn_mesh=mesh,
            kv_page_table=page_tables)
        logits = logits.astype(jnp.float32)
        if eng.temperature == 0.0:
            pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            acc, out = greedy_accept(pred, tokens, draft_len)
        else:
            from deepspeed_tpu.inference.sampling import filtered_logits
            probs = jax.nn.softmax(
                filtered_logits(logits, eng.temperature, eng.top_k,
                                eng.top_p), axis=-1)
            acc, out, key = rejection_accept(probs, tokens, draft_len,
                                             q_dists, key)
        return acc, out, key, eng._pin_cache(cache)

    # -- host API -----------------------------------------------------------

    def draft_len(self):
        """Current global draft length j (1..k) for the next round."""
        return self._j

    def observe(self, active_rows, drafted, accepted_drafts, emitted):
        """Per-round controller + counters. ``drafted`` / ``accepted_
        drafts`` count DRAFT tokens proposed/accepted over the round's
        ``active_rows``; ``emitted`` counts every token the scheduler
        consumed (accepted drafts + one correction/bonus per row). With
        ``min_accept_to_grow > 0`` the draft length grows by one
        toward k while the round's mean acceptance clears the
        threshold and shrinks (floor 1) when it doesn't — draft_len is
        traced data, so adaptation costs zero recompiles. The default
        0.0 pins j = k."""
        self.rounds += 1
        self.row_rounds += int(active_rows)
        self.drafted_total += int(drafted)
        self.accepted_total += int(accepted_drafts)
        self.emitted_total += int(emitted)
        if self.min_accept_to_grow > 0 and active_rows:
            mean = accepted_drafts / float(active_rows)
            if mean >= self.min_accept_to_grow:
                self._j = min(self.k, self._j + 1)
            else:
                self._j = max(1, self._j - 1)

    def draft(self, tokens, positions, page_tables):
        """One compiled draft step: ``[max_batch]`` tokens/positions in,
        ``(next_tokens, q_dist_or_None)`` out (numpy). ``q`` is the
        filtered draft distribution each token was sampled from
        (None for greedy engines — exact match needs no q)."""
        eng = self.engine
        t = jnp.asarray(np.asarray(tokens, np.int32))
        p = jnp.asarray(np.asarray(positions, np.int32))
        args = [eng.params, eng.cache, t, p,
                jnp.asarray(np.asarray(page_tables, np.int32)),
                eng._sample_key]
        if eng.temperature == 0.0:
            nxt, eng._sample_key, eng.cache = self._draft(*args)
            return np.asarray(nxt), None
        nxt, q, eng._sample_key, eng.cache = self._draft(*args)
        return np.asarray(nxt), np.asarray(q)

    def _q_arg(self, q_dists):
        if self.engine.temperature == 0.0:
            # greedy verify never reads q; a fixed tiny dummy keeps the
            # traced signature shape-stable
            return jnp.zeros((1,), jnp.float32)
        return jnp.asarray(np.asarray(q_dists, np.float32))

    def verify(self, tokens, positions, draft_len, page_tables,
               q_dists=None):
        """The one full-depth verify-accept call. ``tokens`` ``[B,
        k+1]`` = ``[pending, d1..dj, pad]``; ``positions`` ``[B, k+1]``
        their absolute slots; ``draft_len`` ``[B]`` real drafts per row
        (0 for inactive rows); ``q_dists`` ``[B, k, V]`` for sampled
        engines. Returns ``(acc_len [B], out_tokens [B, k+1])`` numpy —
        row i emits ``out_tokens[i, :acc_len[i] + 1]``."""
        eng = self.engine
        t = jnp.asarray(np.asarray(tokens, np.int32))
        p = jnp.asarray(np.asarray(positions, np.int32))
        dl = jnp.asarray(np.asarray(draft_len, np.int32))
        acc, out, eng._sample_key, eng.cache = self._verify(
            eng.params, eng.cache, t, p,
            jnp.asarray(np.asarray(page_tables, np.int32)), dl,
            self._q_arg(q_dists), eng._sample_key)
        return np.asarray(acc), np.asarray(out)

    # -- audit surface ------------------------------------------------------

    def draft_lowering_args(self):
        """The exact avals :meth:`draft` calls with — lowering through
        these is a jit-cache hit, never a fresh compile."""
        eng = self.engine
        return (eng.params, eng.cache,
                jnp.zeros((eng.max_batch,), jnp.int32),
                jnp.zeros((eng.max_batch,), jnp.int32),
                jnp.zeros((eng.max_batch, eng.pages_per_row), jnp.int32),
                eng._sample_key)

    def verify_lowering_args(self):
        eng = self.engine
        q = jnp.zeros((1,), jnp.float32) if eng.temperature == 0.0 \
            else jnp.zeros((eng.max_batch, self.k,
                            eng.model.config.vocab_size), jnp.float32)
        return (eng.params, eng.cache,
                jnp.zeros((eng.max_batch, self.k + 1), jnp.int32),
                jnp.zeros((eng.max_batch, self.k + 1), jnp.int32),
                jnp.zeros((eng.max_batch, eng.pages_per_row), jnp.int32),
                jnp.zeros((eng.max_batch,), jnp.int32), q,
                eng._sample_key)

    def draft_hlo(self):
        return self._draft.lower(
            *self.draft_lowering_args()).compile().as_text()

    def verify_hlo(self):
        return self._verify.lower(
            *self.verify_lowering_args()).compile().as_text()

    def facts(self):
        return {
            "k": self.k,
            "draft_layers": self.draft_layers,
            "n_layer": self.engine.model.config.n_layer,
            "min_accept_to_grow": self.min_accept_to_grow,
            "draft_len": self._j,
            "rounds": self.rounds,
            "row_rounds": self.row_rounds,
            "drafted_total": self.drafted_total,
            "accepted_total": self.accepted_total,
            "emitted_total": self.emitted_total,
            # tokens a row advances per compiled round (> 1.0 is the
            # whole point: the non-speculative loop is pinned at 1.0)
            "mean_accepted": (self.emitted_total
                              / float(max(self.row_rounds, 1))),
            # fraction of proposed draft tokens that survived verify
            "draft_efficiency": (self.accepted_total
                                 / float(max(self.drafted_total, 1))),
        }


def build_speculative(engine, config):
    """Parse the ``inference.speculative`` block and hang a
    :class:`SpeculativeDecoder` off the engine — or None when disabled
    OR degenerate (``k == 0`` / ``draft_layers >= n_layer``: a draft
    as deep as the model verifies nothing, so these configs fall back
    to the exact 2-program non-speculative path with no dead third
    compile)."""
    spec_cfg = _cfg_get(config, "speculative", None)
    if not spec_cfg:
        return None
    enabled = bool(_cfg_get(spec_cfg, "enabled", True))
    k = int(_cfg_get(spec_cfg, "k", DEFAULT_SPECULATIVE_K))
    draft_layers = int(_cfg_get(spec_cfg, "draft_layers",
                                DEFAULT_DRAFT_LAYERS))
    grow = float(_cfg_get(spec_cfg, "min_accept_to_grow", 0.0))
    if not enabled or k == 0:
        return None
    from deepspeed_tpu.inference.cache import (refuse_recurrent_or_latent,
                                               refuse_window_ring)
    refuse_window_ring(engine.spec, "inference.speculative",
                       "a rejected draft's keys would have overwritten "
                       "what the ring held of the window before them")
    refuse_recurrent_or_latent(
        engine.spec, "inference.speculative",
        "a rejected draft would have to roll the state back",
        "the verify chunk (several rows of several tokens) has no latent "
        "form, and the draft truncates GPT-2's layers")
    if k < 0:
        raise ValueError(f"speculative k must be >= 0, got {k}")
    n_layer = engine.model.config.n_layer
    if draft_layers == 0:
        draft_layers = n_layer // 2
    if draft_layers >= n_layer or draft_layers <= 0:
        # degenerate depth (including n_layer == 1, where no proper
        # truncation exists): plain decode
        return None
    return SpeculativeDecoder(engine, k, draft_layers,
                              min_accept_to_grow=grow)
