"""Autoregressive decoding with a KV cache for :class:`TransformerLM`.

The reference has no generative model; this completes the framework's LM
family (train with ``tools/train_lm.py``, sample with ``tools/generate.py``).
TPU-first: the whole generation is one jitted program — prompt prefill is a
SINGLE batched causal forward that writes the prompt's K/V into the cache
(one matmul set, not P sequential steps), then a ``lax.scan`` drives the
token loop over static-shape ``(B, KV_heads, S_max, dh)`` buffers written with
``dynamic_update_slice`` at the shared prefix length. Cached decode is
test-verified to reproduce the full-forward logits exactly (teacher-forcing
parity), with f32 score accumulation matching ``ops.attention``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_tpu.models.transformer import TransformerConfig, TransformerLM

__all__ = [
    "init_cache",
    "build_draft_fn",
    "build_generate_fn",
    "decode_step",
    "filter_logits_batched",
    "init_draft_params",
    "make_draft_config",
    "propose_ngram_drafts",
    "propose_ngram_tree",
    "rejection_verify_row",
    "tree_rejection_verify_row",
    "sample_logits",
    "sample_logits_batched",
]

_NEG_INF = -1e30  # matches ops.attention.NEG_INF: masked, not NaN-prone


def sample_logits(logits, key, temperature: float = 0.0,
                  top_k: int | None = None, top_p: float | None = None):
    """One sampling step: ``(B, V) logits → (B,) int32 tokens``.

    ``temperature <= 0`` is greedy argmax (the filters are irrelevant — the
    max always survives both). Otherwise the logits are tempered, then
    ``top_k`` keeps the k highest, then ``top_p`` (nucleus) keeps the
    smallest descending-probability prefix whose cumulative mass reaches
    top_p (the argmax always survives, so the distribution is never empty).
    Static shapes throughout (top_k/sort — no data-dependent control flow),
    so the whole thing jits into the decode scan."""
    if temperature <= 0.0:  # dttlint: disable=jit-purity -- static sampling config: callers pass Python floats, branch specializes the program (see docstring)
        return jnp.argmax(logits, -1).astype(jnp.int32)
    logits = (logits / temperature).astype(jnp.float32)
    if top_k is not None:
        if top_k < 1:  # dttlint: disable=jit-purity -- static sampling config: top_k is a Python int/None at trace time, never a tracer
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        kth = jax.lax.top_k(logits, min(top_k, logits.shape[-1]))[0][..., -1:]
        logits = jnp.where(logits < kth, _NEG_INF, logits)
    if top_p is not None:
        if not 0.0 < top_p <= 1.0:  # dttlint: disable=jit-purity -- static sampling config: top_p is a Python float/None at trace time, never a tracer
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        desc = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep tokens whose EXCLUSIVE prefix mass is < top_p: the first
        # token always qualifies, and the kept set is the smallest prefix
        # with cumulative mass >= top_p.
        n_keep = jnp.sum((cum - probs) < top_p, axis=-1, keepdims=True)
        thresh = jnp.take_along_axis(desc, n_keep - 1, axis=-1)
        logits = jnp.where(logits < thresh, _NEG_INF, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def filter_logits_batched(logits, temperature, top_k, top_p):
    """Tempered + top-k + nucleus FILTERED logits: ``(B, V) → (B, V) f32``
    with per-row params. This is the sampling distribution's definition,
    factored out of :func:`sample_logits_batched` so the rejection-sampling
    speculative verify (:func:`rejection_verify_row`) computes its target
    probabilities from EXACTLY the same filter — distribution parity
    between spec and plain sampled decode holds by construction, not by a
    re-implementation staying in sync.

    ``temperature`` (B,) f32 — rows ``<= 0`` temper at 1.0 (their callers
    go greedy and ignore the filtered logits). ``top_k`` (B,) int32 — rows
    ``< 1`` (or ``>= V``) disable the filter. ``top_p`` (B,) f32 — rows
    outside ``(0, 1]`` disable the filter. Filter semantics match
    :func:`sample_logits` filter-for-filter (temper, then top-k, then
    nucleus on the post-top-k distribution); everything is sorts and
    wheres — no data-dependent shapes."""
    v = logits.shape[-1]
    temperature = temperature.astype(jnp.float32)
    safe_t = jnp.where(temperature > 0.0, temperature, 1.0)
    l = logits.astype(jnp.float32) / safe_t[:, None]
    # top-k as a rank cutoff on the descending sort (mirrors sample_logits'
    # lax.top_k kth-value threshold; disabled rows use k = V, a no-op).
    desc = jnp.sort(l, axis=-1)[:, ::-1]
    k_eff = jnp.clip(jnp.where(top_k >= 1, top_k, v), 1, v)
    kth = jnp.take_along_axis(desc, (k_eff - 1)[:, None], axis=-1)
    l = jnp.where(l < kth, _NEG_INF, l)
    # Nucleus on the post-top-k logits: smallest descending prefix whose
    # EXCLUSIVE cumulative mass is < p (first token always survives).
    # Disabled rows use p = 1.0: filtered-out entries carry ~zero mass, so
    # the kept prefix covers every surviving token — no further filtering.
    p_eff = jnp.where((top_p > 0.0) & (top_p <= 1.0), top_p, 1.0).astype(
        jnp.float32
    )
    desc = jnp.sort(l, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    n_keep = jnp.sum((cum - probs) < p_eff[:, None], axis=-1, keepdims=True)
    thresh = jnp.take_along_axis(desc, n_keep - 1, axis=-1)
    return jnp.where(l < thresh, _NEG_INF, l)


def sample_logits_batched(logits, keys, temperature, top_k, top_p):
    """Traced per-row sampling: ``(B, V) logits → (B,) int32 tokens`` with
    PER-ROW sampling params — the serving engine's slot-batched counterpart
    of :func:`sample_logits` (whose params are Python scalars resolved at
    trace time, so one compiled program serves one sampling config).

    Rows with ``temperature <= 0`` are greedy argmax; the rest draw a
    categorical from :func:`filter_logits_batched`'s filtered logits.
    ``keys`` is a (B,) batch of PRNG keys (one independent stream per row,
    so slots sharing a step draw from unrelated streams). A single busy
    slot in the serving engine reproduces ``tools/generate.py``; the whole
    thing jits into the engine's fixed decode step."""
    greedy = jnp.argmax(logits, -1).astype(jnp.int32)
    l = filter_logits_batched(logits, temperature, top_k, top_p)
    sampled = jax.vmap(jax.random.categorical)(keys, l).astype(jnp.int32)
    return jnp.where(temperature.astype(jnp.float32) > 0.0, sampled, greedy)


def rejection_verify_row(filtered_logits, drafts, seed, made):
    """Lossless rejection-sampling speculative verify for ONE slot's draft
    block (Leviathan et al. / Chen et al., 2023) — the piece that lets
    SAMPLED lanes run speculative decode instead of falling back to plain
    per-token steps.

    ``filtered_logits`` (k+1, V) f32: the TARGET model's logits over the
    draft block, already passed through :func:`filter_logits_batched` with
    the slot's sampling params (position ``j`` conditions on the accepted
    prefix + drafts ``0..j-1``; position ``k`` is the bonus position after
    all drafts). ``drafts`` (k,) int32. ``seed``/``made`` int32 scalars:
    the key for the token at emission offset ``j`` is
    ``fold_in(PRNGKey(seed), made + j)`` — indexed by EMITTED-token count,
    so rounds consume disjoint key indices (a round emitting ``n`` tokens
    advances ``made`` by ``n``; draws computed this round at indices
    ``>= made + n`` are discarded masked lanes and influence nothing).

    The general scheme accepts draft ``i`` with prob ``min(1, p/q)`` and
    resamples the first rejection from the normalized residual
    ``max(0, p - q)``. The repo's drafters (n-gram and the learned draft
    model) propose GREEDILY — ``q`` is a point mass at the drafted token —
    so this is the degenerate (still lossless) case: accept prob is
    ``p(draft)`` and the residual is ``p`` with the drafted token zeroed.
    Each emitted token is marginally an exact draw from ``softmax(
    filtered_logits)`` — the plain sampled-decode distribution.

    Returns ``(emitted (k+1,) int32, accepts (,) int32)``: ``emitted[j]``
    for ``j < accepts`` are the accepted drafts, ``emitted[accepts]`` is
    the residual resample (or the bonus draw when all ``k`` accepted);
    entries past that are junk the engine's length masking discards."""
    s, v = filtered_logits.shape  # s = k + 1
    k = s - 1
    p = jax.nn.softmax(filtered_logits, axis=-1)
    base = jax.random.PRNGKey(seed)
    keys = jax.vmap(lambda j: jax.random.fold_in(base, made + j))(
        jnp.arange(s, dtype=jnp.int32)
    )
    # Two independent streams per key index: sub-key 1 drives the accept
    # uniform, sub-key 2 the resample/bonus categorical.
    u = jax.vmap(
        lambda kj: jax.random.uniform(jax.random.fold_in(kj, 1))
    )(keys[:k])
    accept = u < p[jnp.arange(k), drafts]  # q is one-hot: min(1, p/q) = p
    accepts = jnp.cumprod(accept.astype(jnp.int32)).sum()
    # Residual at the first rejection: p with the drafted token removed,
    # renormalized by the categorical. If the rejected draft held ~all the
    # mass the residual logits are uniformly _NEG_INF and the draw
    # degenerates to token 0 — a measure-≈0 lane (p(draft) ≈ 1 almost
    # always accepts).
    is_draft = jnp.arange(v)[None, :] == drafts[:, None]
    resid = jnp.where(
        is_draft, _NEG_INF, jnp.log(jnp.maximum(p[:k], 1e-38))
    )
    alt_keys = jax.vmap(lambda kj: jax.random.fold_in(kj, 2))(keys)
    resampled = jax.vmap(jax.random.categorical)(
        alt_keys[:k], resid
    ).astype(jnp.int32)
    # All k accepted → the bonus position draws from the target directly
    # (nothing was proposed there, so no residual correction applies).
    bonus = jax.random.categorical(
        alt_keys[k], filtered_logits[k]
    ).astype(jnp.int32)
    alt = jnp.concatenate([resampled, bonus[None]])
    drafts_pad = jnp.concatenate([drafts, jnp.zeros((1,), jnp.int32)])
    j = jnp.arange(s)
    emitted = jnp.where(j < accepts, drafts_pad, alt)
    return emitted, accepts


def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               sharding=None):
    """Static-shape per-layer KV buffers + one shared filled-prefix length.
    Under GQA the buffers hold the UNEXPANDED ``kv_heads`` — the cache (and
    its per-step HBM read, the decode bound past small batches) shrinks by
    the query-group factor. With ``cfg.kv_cache_dtype == 'int8'`` the
    buffers are int8 with per-row f32 scales (another ~2x off the cache
    read at the KV bound, composing with GQA).

    ``sharding`` (a ``jax.sharding.Sharding``) allocates every k/v/scale
    leaf directly under a mesh placement — the sharded serving engine
    passes ``P(None, 'model')`` to split the kv-head axis (axis 1 on every
    leaf) without a replicated round-trip through host memory. The scalar
    ``len`` register stays default-placed."""
    dh = getattr(cfg, "dh", None) or cfg.d_model // cfg.num_heads
    kv = cfg.kv_heads
    quant = getattr(cfg, "kv_cache_dtype", None)
    if quant not in (None, "int8"):
        raise ValueError(f"kv_cache_dtype must be None or 'int8', got {quant!r}")
    dtype = jnp.int8 if quant == "int8" else cfg.compute_dtype

    def zeros(shape, dt):
        if sharding is None:
            return jnp.zeros(shape, dt)
        return jnp.zeros(shape, dt, device=sharding)

    def layer():
        buf = {
            "k": zeros((batch, kv, max_len, dh), dtype),
            "v": zeros((batch, kv, max_len, dh), dtype),
        }
        if quant == "int8":
            buf["k_scale"] = zeros((batch, kv, max_len), jnp.float32)
            buf["v_scale"] = zeros((batch, kv, max_len), jnp.float32)
        return buf

    return {
        # A layer_pattern config: only its attention layers ('*') hold rows
        # (the serving pool adds an 'M' layer's slot state beside them).
        "layers": [layer() if kind == "*" else {} for kind in (
            getattr(cfg, "layer_pattern", None) or "*" * cfg.num_layers)],
        "len": jnp.zeros((), jnp.int32),
    }


def decode_step(model: TransformerLM, params, cache, tok):
    """One cached decode step: ``tok (B, s) int32 → (cache', last-position
    logits (B, V))``. Positions come from the cache's filled length inside
    the model. Factored out of :func:`build_generate_fn`'s token loop so the
    serving engine (``serve/engine.py``) drives the SAME per-token program —
    per-slot under ``jax.vmap``, where the cache's ``len`` becomes a
    per-slot traced scalar and the K/V appends become per-slot scatters."""
    logits, cache = model.apply({"params": params}, tok, cache=cache)
    return cache, logits[:, -1]


def propose_ngram_drafts(history, k: int, ngram: int = 2):
    """Prompt-lookup drafting (host-side, numpy): propose ``k`` candidate
    next tokens by continuing the most recent earlier occurrence of the
    sequence's final n-gram.

    This is the "self-speculative" drafter: no draft model, just the
    request's own prompt + emitted tokens. It backs off from ``ngram`` to
    shorter grams, and pads with the last token when no continuation is
    found. Draft quality only affects SPEED — the engine's verify step
    accepts exactly the longest prefix matching the target model's greedy
    outputs, so a bad draft costs a shorter accepted run, never a wrong
    token."""
    h = np.asarray(history, np.int32).ravel()
    n = int(h.size)
    draft = np.zeros(k, np.int32)
    if n == 0:
        return draft
    draft[:] = h[-1]
    for g in range(min(ngram, n - 1), 0, -1):
        pat = h[n - g:]
        # Most recent earlier occurrence whose continuation exists.
        for j in range(n - g - 1, -1, -1):
            if np.array_equal(h[j : j + g], pat):
                cont = h[j + g : j + g + k]
                if cont.size:
                    draft[: cont.size] = cont
                    return draft
    return draft


def propose_ngram_tree(history, k: int, branches: int,
                       extra_histories=(), ngram: int = 2):
    """Multi-branch prompt-lookup drafting (host-side, numpy): a
    ``(branches, k)`` draft TREE whose row 0 is exactly
    :func:`propose_ngram_drafts` and whose remaining rows are DISTINCT
    alternative continuations of the sequence's final n-gram — first from
    earlier occurrences in the slot's own history, then from
    ``extra_histories`` (the OTHER active slots' histories: slots sharing
    a drafter pool their pattern memory, which is the cross-slot shared
    part of tree speculation — a peer that already emitted the phrase this
    slot is entering donates the continuation as a branch).

    Rows that cannot be filled with a fresh candidate repeat row 0
    (duplicate branches are harmless: greedy accept ties break to the
    lowest row, and the rejection-sampling verify auto-rejects a root
    whose probability mass was already consumed). As with the linear
    drafter, branch quality only affects SPEED, never correctness."""
    if branches < 1:
        raise ValueError(f"branches must be >= 1, got {branches}")
    h = np.asarray(history, np.int32).ravel()
    out = np.zeros((branches, k), np.int32)
    out[:] = propose_ngram_drafts(h, k, ngram=ngram)[None]
    n = int(h.size)
    if n == 0 or branches == 1:
        return out
    cands = []
    for g in range(min(ngram, n - 1), 0, -1):
        pat = h[n - g:]
        # Own history: every earlier occurrence, most recent first.
        for j in range(n - g - 1, -1, -1):
            if np.array_equal(h[j : j + g], pat):
                cont = h[j + g : j + g + k]
                if cont.size:
                    cands.append(cont)
        # Peers: the most recent occurrence per shared history.
        for eh in extra_histories:
            e = np.asarray(eh, np.int32).ravel()
            for j in range(int(e.size) - g, -1, -1):
                if np.array_equal(e[j : j + g], pat):
                    cont = e[j + g : j + g + k]
                    if cont.size:
                        cands.append(cont)
                        break
    seen = {out[0].tobytes()}
    row = 1
    for cont in cands:
        if row >= branches:
            break
        cand = np.full(k, cont[-1], np.int32)
        cand[: cont.size] = cont
        key = cand.tobytes()
        if key not in seen:
            seen.add(key)
            out[row] = cand
            row += 1
    return out


def tree_rejection_verify_row(filtered_logits, tree, seed, made):
    """Lossless rejection-sampling verify for ONE slot's draft TREE — the
    path extension of :func:`rejection_verify_row` (SpecInfer-style
    multi-path verification, arXiv:2305.09781).

    ``filtered_logits`` (N, V) f32 with ``N = 1 + B*D``: row 0 is the
    target distribution after the slot's current token (level 1 — shared
    by every branch root); row ``1 + b*D + j`` conditions on branch ``b``'s
    drafts ``0..j`` (so it is the level ``j + 2`` distribution along that
    branch). ``tree`` (B, D) int32 — row 0 must be the linear drafter's
    block for the pointwise accepted-per-verify guarantee. ``seed``/
    ``made``: the key for emission offset ``j`` is
    ``fold_in(PRNGKey(seed), made + j)`` — same emitted-token-count
    indexing as the linear verify, so rounds consume disjoint indices.

    Level 1 runs SEQUENTIAL multi-candidate rejection sampling over the B
    point-mass roots: accept root ``b`` with prob ``p_res[c_b] / z`` where
    ``p_res`` starts at ``softmax(row 0)`` and each rejection zeroes the
    rejected token's mass out of both ``p_res`` and the remaining mass
    ``z`` (duplicate roots auto-reject: their mass is already 0). The
    per-candidate accept uniform is ``uniform(fold_in(key_0, 3 + b))``;
    if every root rejects, the level-1 token is a categorical from the
    final residual (``fold_in(key_0, 2)``). The accepted marginal is
    exactly ``softmax(row 0)`` — the standard multi-draft result — and
    levels ``2..D`` continue single-candidate verify ALONG the accepted
    branch with the linear scheme's sub-keys (accept ``fold_in(key_j, 1)``,
    residual ``fold_in(key_j, 2)``, bonus at offset D from
    ``fold_in(key_D, 2)``). Streams differ from plain sampled decode (as
    with PR 11's linear verify) but every emitted token is marginally an
    exact draw from the slot's filtered target distribution.

    Returns ``(emitted (D+1,) int32, accepts (,) int32, bsel (,) int32)``:
    ``emitted[:accepts]`` are accepted path drafts, ``emitted[accepts]``
    the residual/bonus draw, ``bsel`` the branch whose KV block the engine
    compacts into the canonical slot timeline (0 when all roots reject —
    its block is junk above the single emitted token and never attended)."""
    n, v = filtered_logits.shape
    b_br, d = tree.shape
    base = jax.random.PRNGKey(seed)
    keys = jax.vmap(lambda j: jax.random.fold_in(base, made + j))(
        jnp.arange(d + 1, dtype=jnp.int32)
    )
    k0 = keys[0]
    p0 = jax.nn.softmax(filtered_logits[0].astype(jnp.float32))
    u_roots = jax.vmap(
        lambda i: jax.random.uniform(jax.random.fold_in(k0, 3 + i))
    )(jnp.arange(b_br, dtype=jnp.int32))

    def try_root(carry, inp):
        p_res, z, chosen = carry
        c, u, i = inp
        acc = (chosen < 0) & (u * z < p_res[c])
        chosen = jnp.where(acc, i, chosen)
        rej = chosen < 0  # nothing accepted through this candidate
        z = jnp.where(rej, z - p_res[c], z)
        p_res = jnp.where(rej, p_res.at[c].set(0.0), p_res)
        return (p_res, z, chosen), None

    (p_res, _, chosen), _ = jax.lax.scan(
        try_root,
        (p0, jnp.float32(1.0), jnp.int32(-1)),
        (tree[:, 0], u_roots, jnp.arange(b_br, dtype=jnp.int32)),
    )
    all_rej = chosen < 0
    t_rej = jax.random.categorical(
        jax.random.fold_in(k0, 2), jnp.log(jnp.maximum(p_res, 1e-38))
    ).astype(jnp.int32)
    bsel = jnp.maximum(chosen, 0).astype(jnp.int32)
    # Levels 2..D: single-candidate verify along the accepted branch.
    path_rows = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         1 + bsel * d + jnp.arange(d, dtype=jnp.int32)]
    )
    filt_path = jnp.take(filtered_logits, path_rows, axis=0)  # (d+1, V)
    drafts_path = jnp.take(tree, bsel, axis=0)  # (d,)
    p_path = jax.nn.softmax(filt_path.astype(jnp.float32), axis=-1)
    u_tail = jax.vmap(
        lambda kj: jax.random.uniform(jax.random.fold_in(kj, 1))
    )(keys[1:d])
    acc_tail = u_tail < p_path[jnp.arange(1, d), drafts_path[1:]]
    accept = jnp.concatenate([(~all_rej)[None], acc_tail])
    accepts = jnp.cumprod(accept.astype(jnp.int32)).sum()
    is_draft = jnp.arange(v)[None, :] == drafts_path[1:, None]
    resid = jnp.where(
        is_draft, _NEG_INF, jnp.log(jnp.maximum(p_path[1:d], 1e-38))
    )
    alt_keys = jax.vmap(lambda kj: jax.random.fold_in(kj, 2))(keys)
    res_tail = jax.vmap(jax.random.categorical)(
        alt_keys[1:d], resid
    ).astype(jnp.int32)
    bonus = jax.random.categorical(
        alt_keys[d], filt_path[d]
    ).astype(jnp.int32)
    alt = jnp.concatenate([t_rej[None], res_tail, bonus[None]])
    drafts_pad = jnp.concatenate([drafts_path, jnp.zeros((1,), jnp.int32)])
    j = jnp.arange(d + 1)
    emitted = jnp.where(j < accepts, drafts_pad, alt)
    return emitted, accepts, bsel


def make_draft_config(cfg: TransformerConfig, num_layers: int,
                      max_seq_len: int | None = None) -> TransformerConfig:
    """The draft model is the target truncated to its first ``num_layers``
    blocks — same widths, same vocab, same embeddings shapes, so
    :func:`init_draft_params` can seed it straight from the target tree
    and the serving engine can share tokenization/eos handling."""
    import dataclasses

    if not 1 <= num_layers <= cfg.num_layers:
        raise ValueError(
            f"draft num_layers {num_layers} outside [1, {cfg.num_layers}]"
        )
    return dataclasses.replace(
        cfg,
        num_layers=num_layers,
        max_seq_len=max_seq_len or cfg.max_seq_len,
    )


def init_draft_params(cfg: TransformerConfig, target_params,
                      num_layers: int):
    """Seed a truncated-layer draft head from the target: the embeddings
    (``tok_embed``/``pos_embed``) are SHARED (and stay frozen under
    ``tools/train_draft.py``'s distillation — the draft reads the target's
    representation space), the first ``num_layers`` blocks plus ``ln_f``
    and ``lm_head`` start as copies and get trained. Returns a plain dict
    tree compatible with ``TransformerLM(make_draft_config(...))``."""
    draft = {}
    for name, sub in target_params.items():
        if name.startswith("block_"):
            if int(name.split("_", 1)[1]) < num_layers:
                draft[name] = sub
        else:
            draft[name] = sub
    return jax.tree_util.tree_map(lambda x: x, draft)


def build_draft_fn(cfg: TransformerConfig, k: int, window: int):
    """Returns ``draft(params, tokens (B, window) int32, lens (B,) int32,
    pos0 (B,) int32) -> (B, k) int32`` — the serving engine's learned
    drafter program.

    Each row is the right-aligned-then-left-packed suffix of a slot's
    history (``tokens[i, :lens[i]]`` real, rest pad; the last real token
    is the slot's current token). Per row: one causal forward of the
    window into a fresh ``window + k`` cache, greedy-pick at ``lens - 1``
    (pad positions never attended — causality keeps position ``j < lens``
    clean), then rewind the cache length to ``lens`` and roll ``k - 1``
    cached greedy steps; the rolls overwrite the pad junk the window
    forward wrote above ``lens`` (write-before-attend makes the stale rows
    unreadable until then).

    ``pos0`` is the ABSOLUTE sequence position of ``tokens[i, 0]``
    (``history_len - lens`` at the engine): the drafter shares the
    target's embeddings, so it must read the same ``pos_embed`` rows (or
    RoPE rotations) the target applies at those positions — the window is
    an attention-context truncation, never a position shift. Training
    (``tools/train_draft.py``) distills with the same absolute-position
    windows. Positions are clamped to ``max_seq_len - 1`` so over-budget
    tail drafts (discarded by the verify anyway) cannot gather out of
    range."""
    if k < 1:
        raise ValueError(f"spec k must be >= 1, got {k}")
    if window < 1:
        raise ValueError(f"draft window must be >= 1, got {window}")
    if window + k > cfg.max_seq_len:
        raise ValueError(
            f"window {window} + k {k} > draft max_seq_len {cfg.max_seq_len}"
        )
    model = TransformerLM(cfg)
    pmax = cfg.max_seq_len - 1

    def draft(params, tokens, lens, pos0):
        def one(toks, ln, p0):
            cache = init_cache(cfg, 1, window + k)
            positions = jnp.minimum(
                p0 + jnp.arange(window, dtype=jnp.int32), pmax
            )
            logits, cache = model.apply({"params": params}, toks[None],
                                        cache=cache,
                                        positions=positions[None])
            t0 = jnp.argmax(
                jnp.take(logits[0], ln - 1, axis=0)
            ).astype(jnp.int32)
            cache = {**cache, "len": ln.astype(jnp.int32)}

            def roll(carry, _):
                cache, tok = carry
                pos = jnp.minimum(p0 + cache["len"], pmax)
                lg, cache = model.apply(
                    {"params": params}, tok[None, None], cache=cache,
                    positions=pos[None, None].astype(jnp.int32),
                )
                nxt = jnp.argmax(lg[0, -1]).astype(jnp.int32)
                return (cache, nxt), tok

            if k == 1:
                return t0[None]
            (_, last), emitted = jax.lax.scan(roll, (cache, t0), None,
                                              length=k - 1)
            return jnp.concatenate([emitted, last[None]])

        return jax.vmap(one)(tokens, lens, pos0)

    return draft


def build_generate_fn(
    cfg: TransformerConfig,
    max_new_tokens: int,
    temperature: float = 0.0,
    cache_len: int | None = None,
    cast_params: bool = True,
    top_k: int | None = None,
    top_p: float | None = None,
):
    """Returns jitted ``generate(params, prompt (B, P) int32, rng) ->
    tokens (B, P + max_new_tokens)``. ``temperature == 0`` is greedy;
    otherwise :func:`sample_logits` applies ``top_k`` then ``top_p``
    filtering before the categorical draw.
    P must be ≥ 1 (conditional generation; the model has no BOS token).
    ``cache_len`` overrides the KV-cache length (default: exactly
    ``P + max_new_tokens``) — benchmarks comparing different generation
    lengths pass a common value so per-step work is identical.
    ``cast_params=False`` keeps the stored f32 tree (the pre-r3 behavior;
    exists so the bench can A/B the cast's measured effect)."""
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    model = TransformerLM(cfg)

    def generate(params, prompt, rng):
        b, p = prompt.shape
        if p < 1:
            raise ValueError("prompt must contain at least one token")
        # Cast params to the compute dtype ONCE, outside the token loop.
        # Flax casts each f32 param at every use, but those casts are
        # loop-invariant and XLA's LICM hoists them out of the scan ANYWAY —
        # the r4 A/B measured the explicit cast worth only ~1% (BASELINE.md
        # decode section). Kept because it documents the intent and guards
        # against a future loop structure that defeats the hoist.
        if cast_params:
            params = jax.tree_util.tree_map(
                lambda t: t.astype(cfg.compute_dtype), params
            )
        max_len = p + max_new_tokens
        if max_len > cfg.max_seq_len:
            raise ValueError(
                f"prompt {p} + {max_new_tokens} new > max_seq_len {cfg.max_seq_len}"
            )
        if cache_len is not None:
            if cache_len < max_len:
                raise ValueError(f"cache_len {cache_len} < needed {max_len}")
            max_len = cache_len
        cache = init_cache(cfg, b, max_len)

        # Prefill: ONE batched causal forward over the whole prompt, filling
        # every layer's K/V at offset 0.
        logits, cache = model.apply({"params": params}, prompt, cache=cache)
        last_logits = logits[:, -1]

        def sample(logits, key):
            return sample_logits(
                logits, key, temperature=temperature, top_k=top_k, top_p=top_p
            )

        def dec(carry, key):
            cache, logits = carry
            tok = sample(logits, key)
            cache, logits = decode_step(model, params, cache, tok[:, None])
            return (cache, logits), tok

        # The final token needs no forward pass — sample it from the last
        # carried logits instead of paying a discarded decode step.
        keys = jax.random.split(rng, max_new_tokens)
        (_, logits), new_tokens = jax.lax.scan(dec, (cache, last_logits), keys[:-1])
        final = sample(logits, keys[-1])[None]
        new_tokens = jnp.concatenate([new_tokens, final], axis=0)
        return jnp.concatenate([prompt, new_tokens.T], axis=1)

    return jax.jit(generate)
