"""Continuous-batching decode engine: fixed shapes, zero recompiles.

The engine has one KV layout: one physical page pool
(:class:`~distributed_tensorflow_tpu.serve.kv_pool.PagedKVPool`) plus
per-slot page tables. The table is a host numpy array passed as a TRACED
operand of fixed shape ``(slots, pages_per_slot)``, so rebinding pages
never retraces; unbound entries point at the reserved trash page, which
absorbs the fixed-shape writes of masked lanes. The prefill, chunk and
verify programs gather a slot's logical ``(kv, max_len, dh)`` cache from
its table row, run the model's B=1 cached forward on it, and scatter
touched pages back. The plain decode program has two ways to reach K and
V (``decode_path``, fixed at construction by
:meth:`SlotEngine._decode_path` from the pool's leaf kinds and dtype, the
page size, the head size and the engine class's hook — no config field or
flag): where its kernel fits, decode attends THROUGH the table and no
logical cache is materialised; elsewhere it gathers like the others.

Jitted programs (all compiled at :meth:`SlotEngine.warmup`, after which
the compile count must never grow — the ``RecompileSentinel`` contract):

* **prefill** — one batched causal forward of a PADDED ``(1, width)``
  prompt where ``width`` is the narrowest compiled bucket (a fixed set,
  ``prefill_buckets``, largest always ``prefill_len``) holding the real
  tokens, then the request's FIRST token sampled at its true last prompt
  position. The forward starts at cache ``len = m0`` where
  ``m0`` tokens of KV were ADOPTED from the prefix cache (copy-free page
  sharing) — only the prompt TAIL is computed, through a tail-sized
  bucket, which is what collapses TTFT for shared-system-prompt traffic.

* **decode step** (``step_fn``) — ONE micro-step over the whole slot
  batch a dispatch (run-ahead, :meth:`SlotEngine.step`, hides the host's
  round behind the device; nothing fuses several steps into a dispatch);
  per-slot traced lengths, per-slot sampling
  (``sample_logits_batched``), inactive lanes masked. The micro-step is
  one of two forwards. ``"table"``: all
  slots as one batch; per layer the new K and V ROW goes straight to
  ``(page_tables[slot, length // page_size], :, length % page_size, :)``
  (a masked lane's to the trash page) and the Pallas kernel
  ``ops.attention.paged_decode_attention`` reads each slot's live pages
  where they lie, up to ``length + 1`` — a slot costs what its live
  length costs and a masked lane nothing. ``"gather"`` (int8 KV, a page
  or head size off the chip's tile, ``ShardedSlotEngine``): ``vmap`` over
  slots of the B=1 cached decode on each slot's gathered logical cache,
  scattering back only the ONE page each slot wrote. Either way the
  write lands in the slot's private boundary page — never a shared prefix
  page, since writes land at positions ``>= p``.

* **speculative verify** (``spec_k > 0``, two compiled variants) — the
  host drafts ``spec_k`` tokens by prompt-lookup (n-gram continuation of
  the slot's own history; ``models/decoding.propose_ngram_drafts``) and
  ONE forward of ``[cur_tok, d_0..d_{k-1}]`` verifies them. All-greedy
  rounds run the greedy variant: the emitted stream is ``targets[:a+1]``
  where ``targets`` are the argmax outputs and ``a`` counts leading
  ``d_i == targets[i]`` matches — each accepted draft equals the token
  greedy decoding would have fed, so by induction the output is
  TOKEN-IDENTICAL to the plain path. Rounds with any sampled lane run
  the rejection-sampling variant (``models/decoding.
  rejection_verify_row``): draft ``i`` is accepted with probability
  ``min(1, p/q)`` against the target's FILTERED distribution (same
  ``filter_logits_batched`` as the plain sampled step) and the first
  rejection resamples from the normalized residual — each emitted token
  is an exact draw from the plain sampled-decode distribution, so
  speculation changes latency, never content (greedy) or the output
  DISTRIBUTION (sampled). Rejected drafts leave stale KV above the
  accepted length, which the overwrite invariant below already makes
  unreadable.

* **tree verify** (``spec_branches > 1``, replaces the linear verify
  programs) — each slot contributes a ``(spec_branches, spec_k)`` draft
  TREE (branch 0 the linear drafter's block; extra branches are
  alternative n-gram continuations pooled across ALL active slots'
  histories — the batch-wide shared draft pool) and ONE widened forward
  of ``1 + B*k`` tokens verifies every branch under a static
  tree-attention ancestor mask. Greedy lanes accept the best branch's
  longest matching path token-identically (ties to branch 0, so
  accepted-per-verify dominates the linear baseline); sampled lanes run
  sequential multi-candidate rejection sampling over the branch roots
  then the linear verify along the winner
  (``models/decoding.tree_rejection_verify_row`` — still lossless). The
  accepted branch's KV block is compacted onto the slot's canonical
  timeline inside the program before the page scatter.

* **chunked prefill** (``prefill_chunk_tokens > 0``) — a
  prompt whose post-adoption tail exceeds the chunk width is fed across
  ENGINE ITERATIONS instead of one forward: full-width
  intermediate chunks through the SAME compiled bucket programs (their
  sampled token is discarded), then one suffix-aligned final chunk whose
  fed window ends exactly at position ``p-1`` so the first token is
  sampled at the true last prompt position. The slot sits in a
  ``PREFILLING`` phase meanwhile (``start`` returns ``(None, False)``)
  and co-resident decode slots keep stepping every iteration —
  Sarathi-style stall-free batching. Because a chunk at offset ``m``
  writes positions ``[m, m+w)`` BEFORE any later chunk attends them
  (write-before-attend, below), resuming at ``len = m`` across separate
  program invocations is exactly as correct as the one-shot tail
  forward. No new programs: chunk calls reuse the bucket set, so the
  zero-recompile contract is untouched.

Drafting (``spec_k > 0``) comes in two flavors behind the same verify:
the zero-weight n-gram prompt-lookup drafter (default), or a LEARNED
draft model (``draft_params``/``draft_cfg``: a truncated-layer head
distilled from the target by ``tools/train_draft.py``) that greedily
rolls ``spec_k`` tokens from a ``draft_window``-token suffix of the
slot's history in one jitted program. Draft quality only moves the
accept rate — the verify forward makes greedy output token-identical
either way.

Correctness invariant for slot reuse (why freed slots are not zeroed, pad
junk is harmless, and rejected-draft KV needs no rollback): after prefill
the filled length is the TRUE prompt length ``p``, and a decode step at
length ``len`` writes position ``len`` BEFORE attending keys ``0..len``
(the cache append precedes the score einsum in ``attention_sublayer``).
By induction every attended key was written by this request — stale rows
sit strictly above the filled length until the step that overwrites them.
``tests/test_serve_engine.py::test_slot_reuse_isolation`` pins this; the
paged/spec parity matrix lives in ``tests/test_paged_kv.py``, the
chunked-prefill parity matrix in ``tests/test_serve_chunked.py``.

An EVA config (``cfg.eva_window`` set; ``models/transformer.
eva_attention_sublayer``) runs the same engine on the pool's composed rows
(``serve/kv_pool.py``: summary pages of a slot's finished windows, then its
current window's pages, then its forming summary pages): decode always goes
through the table (``eva_table_forward``; ``attend`` counts summaries and
window rows, the program also pools the page a token fills into the forming
page); prefill is planned as SEGMENTS of at most a chunk that never cross a
window's end, each through ``eva_prefill_fn`` (the gathered logical rows,
absolute positions for the rotation, summaries of the window's whole chunks
re-formed into the forming pages); between two rounds, or two segments, a
slot that has filled a window is ROLLED on the host (``_roll_window``, span
``engine.window_roll``). ``stats`` counts ``eva_windows_rolled``,
``eva_window_pages_released`` and ``eva_summary_pages_adopted``, and a
round's record carries ``summary_rows_read`` / ``window_rows_read`` and
``eva_summary_writes`` (the lanes whose token filled a chunk: the only
summaries a round writes where the pages fit the page-copy kernel). Slot
export/import, speculation, chunking off and the sharded engine refuse
such a config (``EvaUnsupported``).

A SEGMENTED config is one whose slots carry state beside their K/V rows, or
whose tokens are routed: a CCA config (``cfg.cca_time0`` set;
``models/transformer.cca_attention_sublayer``; the pool's ``cca`` leaf of
every layer), a ``layer_pattern`` config (layers of ONE kind each,
``models/transformer.PatternBlock``: ``M`` a Mamba-2 mixer whose layer holds
the per-slot leaves ``ssm`` and ``conv`` and no pages, ``E`` routed experts
that hold nothing, ``*`` attention that holds the pages, so one page table a
slot serves the ``*`` layers alone), and any config with routed experts
(``cfg.num_experts``; ``models/moe.py``). It runs the same engine; the state
leaves ride the donated pool through every program as the pages do
(``serve/kv_pool.py``), so a round run ahead is queued from the round
before's state with no host read. Decode always goes through the table
(``table_forward``, which also tells the model which lanes are live: a masked
lane leaves its state alone and reaches no expert); prefill is planned as
segments that never overlap (``_start_segments``), each through
``segment_prefill_fn``, which reads zeros for the state at position 0 (a
reused slot starts from zeros), writes the state behind the segment's last
real token and keeps the padding from the experts: a prompt prefilled in
chunks gives the logits of one prefilled whole. With routed experts
``step_fn`` returns three counts beside its tokens, which ``engine.round``
and ``stats`` carry (``experts_touched``, ``expert_tokens_max``, of (token,
expert) pairs; ``moe_tokens_routed``, ``moe_experts_touched``); with a
recurrent state ``engine.round`` carries ``ssm_lanes``,
``engine.prefill_chunk`` ``ssm_blocks`` and ``stats`` ``ssm_state_bytes`` and
``ssm_tokens_scanned``. The prefix cache (an adopted boundary would need a
snapshot of the state), speculation and the sharded engine refuse a
segmented config, and slot export/import one with slot state
(``SlotStateUnsupported``; ``CcaUnsupported`` is its older name).

A latent-attention config (``cfg.kv_lora_rank``: DeepSeek-V2's MLA,
``models/transformer.mla_attention_sublayer``) holds one leaf a layer,
``lat``, a position's latent row, and takes the PLAIN page path and its
prefix cache even with routed experts behind it (the plain programs tell
the model which rows are padding or masked lanes, ``route_mask``):
``decode_path`` "table" (the absorbed paged kernel), ``prefill_path``
"latent" (a chunk attends its gathered row expanded or absorbed by its
width; given no ``prefill_buckets`` the engine compiles the widths
``mla_prefill_widths`` derives from the two forms' counts).
``engine.round`` carries ``latent_rows``, ``engine.start`` and
``engine.prefill_chunk`` ``mla_form`` and ``latent_rows_attended``.
Speculation, the sharded engine and slot export/import refuse it.

Tracing (``obs/trace.py``; always on, no switch): the host side of a round
closes ``engine.round`` around ``engine.prefill_chunk`` (one per chunk
spent), ``engine.dispatch`` (entry of the decode round to the jitted call's
return: the device has work queued from here), ``engine.wait`` (the first
blocking read of the round's outputs: the device finishing) and
``engine.readback`` (the remaining copies and host bookkeeping), and
notes what the round works on: ``active``, ``live_tokens``, ``chunks_run``
and ``kv_rows_read`` (the K and V positions per layer a micro-step of its
decode program reads: the active slots' live pages through the table,
``slots * max_len`` wherever a program gathers), and ``kv_copies`` and
``kv_pages`` (those positions in pages, and the copies a layer that bring
them in: the paged kernel's chain starts one descriptor for a step of
neighbouring pages, a gather takes a page an index; ``stats`` sums both),
and ``kv_row_writes`` (the lanes whose new K and V rows the round wrote, a
layer: ``ops.attention.paged_row_write`` copies each such lane's page in and
out, and a masked lane writes nothing; ``stats`` sums it);
``engine.start`` covers an admission and ``engine.warmup`` the program set's
compiles. ``engine.round`` also says ``ahead``: whether the round whose
tokens the call returns was queued before the round before it was read
(``stats["rounds_ahead"]`` counts those dispatches). The time from the end
of one round's ``engine.wait`` to the end of the next round's
``engine.dispatch`` is the host's work between two rounds; the device has
nothing queued in it only where the round was not run ahead. Inside the
programs, ``jax.named_scope`` names the
phases (``kv.gather``, ``attn``, ``mlp``, ``lm_head``, ``sample``,
``kv.scatter``) in the op metadata a profile shows; scopes change no
program.

Host/device split: the big pool buffers live on device and are DONATED
through every program (in-place turnover). The per-slot registers
(lengths, current token, sampling params, budgets, token history for the
drafter) are small host numpy arrays, the scheduler's view; the plain
decode program carries ``active, lengths, tok, made`` through itself, so
the device holds a copy of them that is one round NEWER than the host's
while a round is in flight (run-ahead of depth one, :meth:`SlotEngine.
step`): a round is queued from the device's copy wherever the host has
changed nothing since the last dispatch, and from the host's, copied and
uploaded once, wherever it has. Those four are read back after they were
fed to the next round, so they are never donated; the pool's leaves are.
"""

from __future__ import annotations

import contextlib
import dataclasses
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_tpu.models.decoding import (
    build_draft_fn,
    decode_step,
    filter_logits_batched,
    propose_ngram_drafts,
    propose_ngram_tree,
    rejection_verify_row,
    sample_logits_batched,
    tree_rejection_verify_row,
)
from distributed_tensorflow_tpu.models.transformer import (
    EvaUnsupported,
    SlotStateUnsupported,
    TransformerLM,
    mla_prefill_form,
    mla_prefill_widths,
)
from distributed_tensorflow_tpu.obs import trace as _trace
from distributed_tensorflow_tpu.ops.attention import (
    chunk_flash_fits,
    paged_decode_copies,
    paged_decode_fits,
    paged_decode_form,
)
from distributed_tensorflow_tpu.serve.kv_pool import (
    TRASH_PAGE,
    InsufficientPages,
    PagedKVPool,
    PrefixCache,
)

__all__ = ["SlotEngine", "ShardedSlotEngine"]


@dataclasses.dataclass
class _Round:
    """A decode round that is queued on the device and not yet read."""

    # The program's outputs after the pool, still on the device: (active,
    # lengths, tok, made, toks, valid) and a verify round's accepted counts.
    out: tuple
    # Host copies of the ``active`` and ``lengths`` the round ran with. A
    # round queued from the registers of the round before it learns them
    # when that round is read.
    was_active: np.ndarray | None
    lengths: np.ndarray | None
    ahead: bool  # queued before the round before it was read
    spec: bool  # a verify round
    any_sampled: bool
    # Routed experts, read with the round: (layer, expert) pairs that got a
    # token, the most tokens one got, the tokens routed. None without them.
    moe: np.ndarray | None = None


class SlotEngine:
    """Fixed-capacity continuous-batching engine over one model replica.

    Drive it with :class:`~distributed_tensorflow_tpu.serve.scheduler.
    Scheduler` (request queue + admission control) or directly:
    ``acquire_slot`` → ``start`` (prefill, returns the first token) →
    repeated ``step`` (one batch round; token count varies — plain rounds
    yield one row, speculative rounds up to ``spec_k+1``)
    → ``release``. Single-threaded by contract: one thread owns the
    engine. ``start`` raises :class:`InsufficientPages` when the pool
    cannot back the request right now — release the slot and retry
    once in-flight requests free pages.
    """

    def __init__(
        self,
        cfg,
        params,
        *,
        slots: int = 4,
        max_len: int | None = None,
        prefill_len: int | None = None,
        sentinel=None,
        page_size: int | None = None,
        kv_pages: int = 0,
        prefix_cache: bool = True,
        spec_k: int = 0,
        spec_branches: int = 1,
        prefill_buckets: tuple = (),
        prefill_chunk_tokens: int = 0,
        draft_params=None,
        draft_cfg=None,
        draft_window: int = 16,
    ):
        max_len = int(max_len or cfg.max_seq_len)
        prefill_len = int(prefill_len or max(1, max_len // 2))
        if max_len > cfg.max_seq_len:
            raise ValueError(
                f"max_len {max_len} > model max_seq_len {cfg.max_seq_len}"
            )
        if not 1 <= prefill_len <= max_len:
            raise ValueError(
                f"prefill_len {prefill_len} outside [1, max_len {max_len}]"
            )
        if page_size is None:
            # One whole-row page per slot when 16 doesn't divide max_len,
            # rather than erroring.
            page_size = 16 if max_len % 16 == 0 else max_len
        if page_size <= 0:
            # Arrives from outside (--page_size, a ServeConfig file).
            raise ValueError(
                f"page_size must be a positive divisor of max_len "
                f"{max_len} (or None: 16 where it divides max_len, else "
                f"one page of max_len), got {page_size}"
            )
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        spec_branches = int(spec_branches)
        if spec_branches < 1:
            raise ValueError(
                f"spec_branches must be >= 1, got {spec_branches}"
            )
        if spec_branches > 1:
            if not spec_k:
                raise ValueError("spec_branches > 1 requires spec_k > 0")
            if getattr(cfg, "attention_window", None) is not None:
                # Tree verify feeds a non-chain block: in-block positions
                # are non-monotone in cache-write order, which the sliding
                # window's relative-offset mask cannot express.
                raise ValueError(
                    "spec_branches > 1 (tree speculation) is incompatible "
                    "with attention_window"
                )
            if 1 + spec_branches * spec_k > max_len - 1:
                raise ValueError(
                    f"tree verify width 1 + {spec_branches}*{spec_k} "
                    f"exceeds max_len - 1 ({max_len - 1}); shrink "
                    "spec_branches/spec_k"
                )
        self._eva = bool(getattr(cfg, "eva", False))
        if self._eva:
            # What is not extended to the composed table refuses here, by
            # name, rather than serving something else.
            if spec_k:
                raise EvaUnsupported(
                    "speculation (spec_k > 0, tree or linear) is not "
                    "extended to EVA: a rejected draft would have to roll "
                    "back summaries and window rolls")
            if getattr(self, "tp", 1) > 1:
                raise EvaUnsupported("ShardedSlotEngine has no EVA path")
            c = int(prefill_chunk_tokens) or prefill_len
            if c < 0 or int(cfg.eva_window) % c:
                raise EvaUnsupported(
                    f"an EVA config needs chunked prefill with a chunk "
                    f"({c}) that divides eva_window {cfg.eva_window}: a "
                    f"prefill segment never straddles a window")
        self._cca = bool(getattr(cfg, "cca", False))
        self._ssm = bool(getattr(cfg, "ssm", False))
        self._moe = bool(getattr(cfg, "num_experts", 0))
        self._mla = bool(getattr(cfg, "mla", False))
        # Configs whose prefill is planned as padded segments that never
        # overlap and whose decode always goes through the table, with the
        # live lanes and real rows told to the model: slots that carry state
        # beside their rows (CCA, a Mamba-2 layer), routed experts (an idle
        # lane or a padding row must reach none), any layer_pattern. Latent
        # attention with routed experts holds nothing but pages: it takes
        # the plain page path and its prefix cache, the routing mask told
        # to the model by the plain programs.
        self._segmented = (
            self._cca or (self._moe and not self._mla)
            or getattr(cfg, "layer_pattern", None) is not None)
        if self._mla:
            # What latent rows are not extended to refuses here, by name
            # (int8 pages and weight quantisation refuse in the config).
            if spec_k:
                raise SlotStateUnsupported(
                    "speculation (spec_k > 0) is not extended to latent "
                    "attention: its verify programs attend K and V rows")
            if getattr(self, "tp", 1) > 1:
                raise SlotStateUnsupported(
                    "ShardedSlotEngine has no path for latent attention: "
                    "no tensor parallel rule splits a latent row")
        if self._segmented:
            # What the slot state and the segment plan are not extended to
            # refuses here, by name, rather than serving something else.
            what, why_spec, why_prefix = (
                ("CCA", "a rejected draft would have to roll the convolution "
                 "state back", "an adopted boundary needs a snapshot of the "
                 "convolution state at it") if self._cca
                else ("a recurrent state", "a rejected draft would have to "
                      "roll the state back", "an adopted boundary needs a "
                      "snapshot of the state at it") if self._ssm
                else ("routed experts", "the verify programs do not keep "
                      "idle lanes from the experts", "their prefill is "
                      "planned as padded segments, which adopt nothing"))
            if spec_k:
                raise SlotStateUnsupported(
                    f"speculation (spec_k > 0) is not extended to {what}: "
                    f"{why_spec}")
            if prefix_cache:
                raise SlotStateUnsupported(
                    f"the prefix cache is not extended to {what}: "
                    f"{why_prefix} (pass prefix_cache=False)")
            if getattr(self, "tp", 1) > 1:
                raise SlotStateUnsupported(
                    "ShardedSlotEngine has no path for CCA, a recurrent "
                    "state or routed experts")
        self.cfg = cfg
        # Place params through the same path swap candidates stage through
        # (``_place_params``): a checkpoint bundle arrives as host numpy,
        # and numpy vs device-array arguments key DIFFERENT pjit cache
        # entries — boot params must look exactly like adopted ones or the
        # first post-swap round grows the compile caches (the poll-mode
        # sentinel counts that as a recompile) and re-uploads weights every
        # dispatch until then.
        with _trace.span("engine.place_weights"):
            self.params = jax.block_until_ready(self._place_params(params))
        self.model = TransformerLM(cfg)
        self.slots = int(slots)
        self.max_len = max_len
        self.prefill_len = prefill_len
        self.page_size = int(page_size)
        self.spec_k = int(spec_k)
        self.spec_branches = spec_branches
        # Positions a verify round writes above each slot's length: the
        # whole fed block. _decode_round's end-of-window fallback guard
        # uses this (tree blocks are wider than linear ones).
        self._spec_write = (
            1 + spec_branches * self.spec_k
            if spec_branches > 1
            else self.spec_k + 1
        )
        # Prefill width buckets: the prefill program is shape-polymorphic
        # in its tokens width, so a FIXED set of widths is just a fixed set
        # of compiled programs — warmup compiles every member and the
        # zero-recompile invariant is untouched. A request
        # whose post-adoption tail fits a narrow bucket prefills through
        # it instead of paying the full prefill_len-wide forward; this is
        # what turns prefix-cache hits into TTFT wins (without buckets the
        # padded tail costs the same compute as a cold prompt). The
        # largest bucket is always prefill_len — the cold-prompt path.
        # Latent attention given none takes the widths its two forms call
        # for (models/transformer.mla_prefill_widths).
        buckets = {int(b) for b in prefill_buckets}
        if not buckets and self._mla:
            buckets = set(mla_prefill_widths(cfg, max_len, prefill_len))
        for b in buckets:
            if not 1 <= b <= prefill_len:
                raise ValueError(
                    f"prefill bucket {b} outside [1, prefill_len "
                    f"{prefill_len}]"
                )
        buckets.add(prefill_len)
        # Chunked prefill: 0 = auto (chunk width =
        # prefill_len, i.e. prompts up to prefill_len keep the one-shot
        # path byte-for-byte and only LONGER prompts chunk), -1 = off
        # (prefill_len stays a hard prompt cap, the pre-chunking
        # contract). Widths above the chunk are pruned from the bucket
        # set — the one-shot path never sees a tail wider than the chunk
        # once chunking is on, so they would be dead compiled programs.
        c = int(prefill_chunk_tokens)
        if c >= 0:
            if c == 0:
                c = prefill_len
            if not 1 <= c <= prefill_len:
                raise ValueError(
                    f"prefill_chunk_tokens {c} outside [1, prefill_len "
                    f"{prefill_len}]"
                )
            buckets = {b for b in buckets if b <= c}
            buckets.add(c)
        else:
            c = -1
        self.prefill_chunk_tokens = c
        self.prefill_buckets = tuple(sorted(buckets))
        # Learned drafter (optional): a small draft LM rolled greedily for
        # spec_k tokens from a draft_window-token suffix of each slot's
        # history — one jitted program, compiled at warmup alongside the
        # verify. Replaces the host n-gram drafter when provided; the
        # verify loop (and therefore token-identical greedy output) is
        # unchanged either way.
        if draft_params is not None:
            if not self.spec_k:
                raise ValueError("draft_params requires spec_k > 0")
            if draft_cfg is None:
                raise ValueError("draft_params requires draft_cfg")
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size}"
                )
            # The draft cache holds window + spec_k positions; clamp the
            # window so it fits the draft model's trained length.
            draft_window = min(
                int(draft_window), draft_cfg.max_seq_len - self.spec_k
            )
            if draft_window < 1:
                raise ValueError(
                    f"draft max_seq_len {draft_cfg.max_seq_len} too short "
                    f"for spec_k {self.spec_k}"
                )
        self.draft_cfg = draft_cfg
        self.draft_params = draft_params
        self.draft_window = int(draft_window)
        self.drafter = "model" if draft_params is not None else "ngram"
        # Optional obs.perf.RecompileSentinel: fed the compile-cache size
        # after warmup and every round, it turns the zero-recompile
        # invariant into the alerting ``recompile_events_total`` metric.
        self.sentinel = sentinel
        # Deploy surface (serve/deploy/): the checkpoint step currently
        # serving and the named variant it belongs to. adopt_weights()
        # maintains both; /healthz and the fleet registry report them.
        self.weight_version = 0
        self.serving_variant = ""
        # Mesh topology: the base engine is one fully-replicated process.
        # ShardedSlotEngine sets these BEFORE delegating here so the pool
        # and program hooks below see them.
        if not hasattr(self, "tp"):
            self.tp = 1
            self.mesh = None
        self.pool = self._build_pool(cfg, max_len, kv_pages)
        self.prefix = PrefixCache(self.pool) if prefix_cache else None
        self.decode_path = self._decode_path()
        self.decode_kernel_form = self._decode_kernel_form()
        self.prefill_path = self._prefill_path()

        # Per-slot host registers. Fixed dtypes — the jit signatures (and
        # therefore the zero-recompile guarantee) depend on them.
        n = self.slots
        self.active = np.zeros(n, bool)
        self.lengths = np.zeros(n, np.int32)  # filled cache prefix per slot
        self.cur_tok = np.zeros(n, np.int32)  # last sampled, next to feed
        self.temp = np.zeros(n, np.float32)
        self.top_k = np.zeros(n, np.int32)
        self.top_p = np.zeros(n, np.float32)
        self.seed = np.zeros(n, np.uint32)
        self.made = np.zeros(n, np.int32)  # tokens generated so far
        self.budget = np.ones(n, np.int32)  # max_new_tokens per slot
        self.eos = np.full(n, -1, np.int32)  # -1 = no eos stop
        # Prompt + emitted tokens per slot — the drafter's corpus. Bounded
        # by max_len (prompt + budget <= max_len is validated at start).
        self.history = np.zeros((n, max_len), np.int32)
        self.hist_len = np.zeros(n, np.int32)
        # PREFILLING phase state: slots mid-chunked-prefill are neither
        # free nor active. _pf holds each one's chunk plan; _pf_queue is
        # the round-robin order chunks are spent in.
        self.prefilling = np.zeros(n, bool)
        self._pf: dict[int, dict] = {}
        self._pf_queue: deque[int] = deque()
        # Cumulative fast-path counters; the scheduler mirrors these into
        # ServingMetrics (serve_prefix_hit_rate / serve_spec_accept_rate).
        # The aggregate spec keys stay (pre-drafter dashboards); the
        # per-drafter keys feed the drafter-labeled /metrics counters.
        self.stats = {
            "prefix_tokens_matched": 0,
            "prefix_tokens_total": 0,
            "spec_drafts_accepted": 0,
            "spec_drafts_proposed": 0,
            "spec_drafts_accepted_ngram": 0,
            "spec_drafts_proposed_ngram": 0,
            "spec_drafts_accepted_model": 0,
            "spec_drafts_proposed_model": 0,
            "spec_rounds": 0,
            "spec_rounds_sampled": 0,
            "spec_verifies": 0,
            "plain_rounds": 0,
            "rounds_ahead": 0,
            # Over the decode rounds read so far: the copies a layer that
            # brought their K and V pages in, and those pages.
            "kv_copies": 0,
            "kv_pages_copied": 0,
            # Over the decode rounds read so far: the lanes whose new K and V
            # rows a round wrote (a layer), and on EVA the lanes whose token
            # filled a chunk, whose summary it wrote.
            "kv_row_writes": 0,
            "eva_summary_writes": 0,
            "prefill_chunks": 0,
            "prefill_tokens_last_iter": 0,
            "eva_windows_rolled": 0,
            "eva_summary_pages_adopted": 0,
            "eva_window_pages_released": 0,
            # Routed experts, over the decode rounds read so far: (token,
            # expert) pairs that reached a held expert, and (layer, expert)
            # pairs with a token.
            "moe_tokens_routed": 0,
            "moe_experts_touched": 0,
            # A recurrent state: what the pool holds of it (no counter), and
            # the prompt tokens the prefill segments scanned.
            "ssm_state_bytes": self.pool.state_bytes if self._ssm else 0,
            "ssm_tokens_scanned": 0,
            # No counter: fixed with the decode program, kept here for
            # whoever reads the rounds' counts beside it.
            "decode_kernel_form": self.decode_kernel_form,
            "prefill_path": self.prefill_path,
        }
        # EVA: positions each slot's request ends at (prompt + budget),
        # which sizes the pages a window roll binds.
        self._eva_total = np.zeros(n, np.int64)
        # Per-slot accepted-draft counts, one sample per (slot, verify
        # round) — loadgen/metrics read accepted-per-verify p50/p99 off
        # this bounded window.
        self.accept_samples: deque[int] = deque(maxlen=4096)
        self._force_plain = False  # warmup hook: compile the non-spec path
        # Run-ahead of depth one (see step()): the round that is queued on
        # the device and not yet read, the round the last step() returned,
        # the slots the host has changed since the last dispatch, and the
        # device copies of what only the host changes (sampling parameters,
        # budget, eos, the page table), as uploaded with that dispatch.
        self._flight: _Round | None = None
        self._returned: _Round | None = None
        # What the last one-shot prefill notes on engine.start (latent
        # attention's form and rows; empty elsewhere).
        self._mla_note: dict = {}
        self._touched = np.zeros(n, bool)
        self._dev_consts: tuple = ()

        model = self.model
        ps, pps = self.page_size, self.pool.pages_per_slot

        # -- page plumbing -----------------------------------------------
        # A slot's logical cache is the gather of its table row; the
        # inverse reshape splits a logical buffer back into pages. Both
        # are layout-generic over the cache leaf kinds (k/v rows
        # (pages, kv, ps, dh) and int8 scales (pages, kv, ps)).

        def gather_row(buf, row):
            g = jnp.swapaxes(buf[row], 0, 1)  # (kv, pps, ps[, dh])
            return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])

        def split_pages(x):
            # (kv, max_len[, dh]) -> (pps, kv, ps[, dh])
            x = x.reshape((x.shape[0], pps, ps) + x.shape[2:])
            return jnp.swapaxes(x, 0, 1)

        def gather_cache(pool_layers, row, length):
            with jax.named_scope("kv.gather"):
                return {
                    "layers": [
                        {k: gather_row(v, row)[None] for k, v in l.items()}
                        for l in pool_layers
                    ],
                    "len": length,
                }

        def make_prefill(sampled: bool):
            def prefill_fn(
                pool_layers, params, tokens, length, prefix_len, row,
                temp, top_k, top_p, seed,
            ):
                """Tail prefill into the slot's pages. ``prefix_len`` (m0,
                a page multiple, traced) tokens of KV are already present
                via adopted shared pages; the forward runs the padded tail
                at cache ``len = m0`` so positions/rotations line up, and
                the first token is sampled at the true last prompt
                position ``length - 1`` (tail-local index
                ``length - m0 - 1``). The scatter-back writes EVERY page
                in the row: adopted pages round-trip their gathered values
                (byte-identical — the forward never writes below m0) and
                unbound tail entries land in the trash page."""
                cache = gather_cache(pool_layers, row, prefix_len)
                if self.prefill_path == "flash":
                    # The cached branch attends the chunk by blocks where
                    # the cache says so, as "pages" says the table path.
                    cache["flash"] = True
                if self._moe:
                    # The padding behind the real rows reaches no expert.
                    cache["route_mask"] = (jnp.arange(tokens.shape[1])
                                           < length - prefix_len)[None]
                logits, cache = model.apply(
                    {"params": params}, tokens, cache=cache,
                    logit_rows=(length - prefix_len - 1)[None],
                )
                first = _select(
                    sampled, logits[0, 0], temp, top_k, top_p, seed)
                with jax.named_scope("kv.scatter"):
                    new_pool = [
                        {
                            k: pl[k].at[row].set(split_pages(cl[k][0]))
                            for k in pl
                        }
                        for pl, cl in zip(pool_layers, cache["layers"])
                    ]
                return new_pool, first

            return prefill_fn

        def make_eva_prefill(sampled: bool):
            w, spw = self.pool.window, self.pool.sum_pages
            n_sum = spw * ps  # summaries of one window

            def eva_prefill_fn(
                pool_layers, params, tokens, n_real, abs_start, row,
                temp, top_k, top_p, seed,
            ):
                """One prefill segment of an EVA slot: ``n_real`` tokens
                (padded to the bucket) at absolute position ``abs_start``,
                all inside one window. The slot's LOGICAL rows are gathered
                from its composed row (summaries of finished windows, then
                the window so far; a bucket's worth of trash entries behind
                it takes the padding's junk rows), the segment appends
                behind them and attends causally over logical rows
                (``eva_attention_sublayer``), every page of the row is
                scattered back, and the summaries of the window's whole
                chunks so far go to the forming pages (the row's last
                entries): recomputed from the rows, so that chunks adopted
                with a prefix are covered too."""
                width = tokens.shape[1]
                table = jnp.concatenate([
                    row[:pps],
                    jnp.full((-(-width // ps),), TRASH_PAGE, row.dtype),
                ])
                win_base = (abs_start // w) * n_sum
                cache = gather_cache(
                    pool_layers, table, win_base + abs_start % w)
                cache["win_base"] = win_base
                positions = abs_start + jnp.arange(width, dtype=jnp.int32)
                logits, cache = model.apply(
                    {"params": params}, tokens, cache=cache,
                    positions=positions[None],
                    logit_rows=(n_real - 1)[None],
                )
                first = _select(
                    sampled, logits[0, 0], temp, top_k, top_p, seed)
                ci = jnp.arange(n_sum)
                whole = ci < (abs_start % w + n_real) // ps
                page = jnp.where(whole, row[pps + ci // ps], TRASH_PAGE)
                with jax.named_scope("kv.scatter"):
                    new_pool = []
                    for pl, cl in zip(pool_layers, cache["layers"]):
                        layer = {}
                        for name in pl:
                            logical = cl[name][0]  # (kv, rows, dh)
                            kv, dh = logical.shape[0], logical.shape[-1]
                            leaf = pl[name].at[table].set(jnp.swapaxes(
                                logical.reshape(kv, -1, ps, dh), 0, 1))
                            rows = (
                                (page[:, None] * kv + jnp.arange(kv)[None, :])
                                * ps + (ci % ps)[:, None]
                            ).reshape(-1)
                            sums = jnp.swapaxes(cl["sum_" + name][0], 0, 1)
                            layer[name] = leaf.reshape(-1, dh).at[rows].set(
                                sums.reshape(-1, dh)).reshape(leaf.shape)
                        new_pool.append(layer)
                return new_pool, first

            return eva_prefill_fn

        def make_segment_prefill(sampled: bool):
            state_leaves = self.pool.state_leaves

            def segment_prefill_fn(
                pool_layers, params, tokens, n_real, abs_start, row, slot,
                temp, top_k, top_p, seed,
            ):
                """One prefill segment of a slot of a segmented config:
                ``n_real`` tokens (padded to the bucket) at position
                ``abs_start``, appended behind the slot's gathered rows (a
                bucket's worth of trash entries behind the row takes the
                padding's junk rows). The slot's state (the pool's ``cca``,
                or ``ssm`` and ``conv``, leaves of the layers that have
                them, row ``slot``) is read where the segment continues a
                prompt and is ZERO where it starts one (``abs_start`` 0: a
                reused slot starts from zeros), and is written back as it
                stands behind the last real token: the next segment, or the
                first decode round, continues from it. Padding reaches no
                expert."""
                width = tokens.shape[1]
                table = jnp.concatenate([
                    row, jnp.full((-(-width // ps),), TRASH_PAGE, row.dtype)])
                cache = gather_cache(
                    [{k: l[k] for k in ("k", "v") if k in l}
                     for l in pool_layers], table, abs_start)
                for cl, pl in zip(cache["layers"], pool_layers):
                    for name in state_leaves:
                        if name in pl:
                            cl[name] = jnp.where(
                                abs_start == 0, 0, pl[name][slot])[None]
                cache["n_real"] = n_real[None]
                cache["route_mask"] = (jnp.arange(width) < n_real)[None]
                if self.prefill_path == "flash":
                    cache["flash"] = True
                logits, cache = model.apply(
                    {"params": params}, tokens, cache=cache,
                    logit_rows=(n_real - 1)[None],
                )
                first = _select(sampled, logits[0, 0], temp, top_k, top_p, seed)
                with jax.named_scope("kv.scatter"):
                    new_pool = []
                    for pl, cl in zip(pool_layers, cache["layers"]):
                        layer = {}
                        for name in pl:
                            if name in state_leaves:
                                layer[name] = pl[name].at[slot].set(
                                    cl[name][0])
                                continue
                            logical = cl[name][0]  # (kv, rows, dh)
                            kv, dh = logical.shape[0], logical.shape[-1]
                            layer[name] = pl[name].at[table].set(jnp.swapaxes(
                                logical.reshape(kv, -1, ps, dh), 0, 1))
                        new_pool.append(layer)
                return new_pool, first

            return segment_prefill_fn

        def _select(sampled, last, temp, top_k, top_p, seed):
            with jax.named_scope("sample"):
                if sampled:  # dttlint: disable=jit-purity -- static program-variant flag: the factory bakes sampled in as a Python bool (one jitted program per variant)
                    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
                    return sample_logits_batched(
                        last[None], key[None], temp[None], top_k[None],
                        top_p[None],
                    )[0]
                return jnp.argmax(last).astype(jnp.int32)

        def make_step(sampled: bool):
            def gather_forward(pool_layers, ptabs, active, lengths, tok, params):
                """Each slot gathers its logical cache from its table row,
                appends one token, and scatters back only the single page
                it wrote (page ``length // page_size`` — always
                slot-private: decode positions are ``>= p``, strictly
                above every shared full prompt page). Inactive lanes
                scatter into the trash page."""

                def one(row, length, t):
                    cache = gather_cache(pool_layers, row, length)
                    cache, logits = decode_step(
                        model, params, cache, t[None, None]
                    )
                    wp = length // ps

                    def grab(x):
                        starts = (0, wp * ps) + (0,) * (x.ndim - 2)
                        sizes = (x.shape[0], ps) + x.shape[2:]
                        return jax.lax.dynamic_slice(x, starts, sizes)

                    with jax.named_scope("kv.scatter"):
                        written = [
                            {k: grab(v[0]) for k, v in l.items()}
                            for l in cache["layers"]
                        ]
                    return written, logits[0]

                written, logits = jax.vmap(one)(ptabs, lengths, tok)
                wp = lengths // ps
                dest = ptabs[jnp.arange(ptabs.shape[0]), wp]
                dest = jnp.where(active, dest, TRASH_PAGE)
                with jax.named_scope("kv.scatter"):
                    pool_layers = [
                        {k: pl[k].at[dest].set(written[li][k])
                         for k in pl}
                        for li, pl in enumerate(pool_layers)
                    ]
                return pool_layers, logits, None

            def table_forward(pool_layers, ptabs, active, lengths, tok, params):
                """All slots as one batch over the pool where it lies: per
                layer the new K and V ROW goes straight to page
                ``length // page_size`` of its slot (a masked lane's to the
                trash page) and attention reads the pages through the
                table up to each slot's live length
                (``models/transformer._attend_through_table``). Nothing is
                gathered and nothing is cut back out."""
                dest = ptabs[jnp.arange(ptabs.shape[0]), lengths // ps]
                cache = {
                    "layers": pool_layers,
                    "len": lengths,
                    "pages": ptabs,
                    "write_page": jnp.where(active, dest, TRASH_PAGE),
                    "attend": jnp.where(active, lengths + 1, 0),
                }
                if self._segmented:
                    # A masked lane feeds no real row: its slot's state
                    # stands, and its token reaches no expert.
                    cache["n_real"] = active.astype(jnp.int32)
                    cache["route_mask"] = active[:, None]
                elif self._moe:
                    cache["route_mask"] = active[:, None]
                cache, logits = decode_step(model, params, cache, tok[:, None])
                return cache["layers"], logits, cache.get("moe_counts")

            def eva_table_forward(pool_layers, ptabs, active, lengths, tok,
                                  params):
                """``table_forward`` on EVA's composed rows
                (``serve/kv_pool.py``): the new row goes to the current
                window's page under ``length``, which lies behind the
                summary pages of the slot's finished windows; a slot
                attends those summaries and its window's rows up to itself;
                and a token that fills a chunk sends the chunk's summary to
                the slot's forming page (the row's last entries)."""
                w, spw = self.pool.window, self.pool.sum_pages
                lanes = jnp.arange(ptabs.shape[0])
                done, off = lengths // w, lengths % w
                dest = ptabs[lanes, done * spw + off // ps]
                form = ptabs[lanes, pps + off // ps // ps]
                fills = active & ((lengths + 1) % ps == 0)
                cache = {
                    "layers": pool_layers,
                    "len": lengths,
                    "pages": ptabs[:, :pps],
                    "write_page": jnp.where(active, dest, TRASH_PAGE),
                    "attend": jnp.where(
                        active, done * (spw * ps) + off + 1, 0),
                    "sum_page": jnp.where(fills, form, TRASH_PAGE),
                }
                cache, logits = decode_step(model, params, cache, tok[:, None])
                return cache["layers"], logits, None

            forward = (
                eva_table_forward if self._eva
                else table_forward if self.decode_path == "table"
                else gather_forward
            )

            def step_fn(
                pool_layers, params, ptabs, active, lengths, tok,
                temp, top_k, top_p, seed, made, budget, eos,
            ):
                """One decode round: one micro-step of the engine's one
                ``forward`` over the pool (``decode_path``: through the
                page table, or by gathering every slot's logical cache).
                Returns the new pool and registers plus the ``(1, slots)``
                sampled tokens and their validity mask (a slot's token is
                valid where it was active at sampling time: the final
                token of a finishing slot is valid). The leading axis is
                :meth:`SlotEngine.step`'s row axis: a verify round fills
                more than one row."""
                pool_layers, logits, counts = forward(
                    pool_layers, ptabs, active, lengths, tok, params
                )
                nxt = _pick(sampled, logits, seed, made, temp, top_k, top_p)
                nxt = jnp.where(active, nxt, tok)
                new_lengths = jnp.where(active, lengths + 1, lengths)
                new_made = jnp.where(active, made + 1, made)
                finished = active & ((new_made >= budget) | (nxt == eos))
                out = (
                    pool_layers, active & ~finished, new_lengths, nxt,
                    new_made, nxt[None], active[None],
                )
                if counts is None:
                    return out
                # Routed experts: (layer, expert) pairs with a token, the
                # most (token, expert) pairs one of them got, the pairs
                # routed; read back with the round's tokens.
                return out + (jnp.stack([
                    (counts > 0).sum(), counts.max(), counts.sum()
                ]).astype(jnp.int32),)

            return step_fn

        def _pick(sampled, logits, seed, made, temp, top_k, top_p):
            with jax.named_scope("sample"):
                if sampled:  # dttlint: disable=jit-purity -- static program-variant flag, as in _select
                    keys = jax.vmap(
                        lambda s, m: jax.random.fold_in(
                            jax.random.PRNGKey(s), m)
                    )(seed, made)
                    return sample_logits_batched(
                        logits, keys, temp, top_k, top_p
                    )
                return jnp.argmax(logits, -1).astype(jnp.int32)

        def make_spec(rs: bool):
            S = self.spec_k + 1

            def spec_fn(
                pool_layers, params, ptabs, active, lengths, tok, drafts,
                temp, top_k, top_p, seed, made, budget, eos,
            ):
                """One speculative verify round. Feeds
                ``[cur_tok, d_0..d_{k-1}]`` (S tokens) per slot in ONE
                forward; ``targets = argmax(logits)`` are the greedy
                continuations after each fed token.

                Greedy lanes (and the whole ``rs=False`` variant): with
                ``a`` = leading ``d_i == targets[i]`` matches, the emitted
                stream is ``targets[:a+1]`` — token-identical to ``a+1``
                plain rounds, because each accepted draft IS the token the
                plain path would have fed next.

                Sampled lanes (``rs=True`` variant, rows with
                ``temp > 0``): rejection-sampling verify
                (``models/decoding.rejection_verify_row``) over the SAME
                forward's logits, filtered with the slot's sampling params
                by the SAME ``filter_logits_batched`` the plain path uses
                — each emitted token is an exact draw from the plain
                sampled-decode distribution (lossless speculation), and
                ``a`` counts the accepted drafts.

                Either way the emitted count is ``a + 1`` before budget /
                eos truncation, so the KV bookkeeping is shared: all S
                positions are written (then truncated by moving
                ``lengths`` up only ``n_final``) — rejected rows sit above
                the filled length, stale-until-overwritten, per the module
                invariant. The whole table row scatters back (shared
                prefix pages get byte-identical values; overrun past the
                slot's bound pages lands in trash)."""

                def one(row, length, t, d):
                    cache = gather_cache(pool_layers, row, length)
                    x = jnp.concatenate([t[None], d])[None]  # (1, S)
                    logits, cache = model.apply(
                        {"params": params}, x, cache=cache
                    )
                    pages = [
                        {k: split_pages(v[0]) for k, v in l.items()}
                        for l in cache["layers"]
                    ]
                    return pages, logits[0]

                pages, logits = jax.vmap(one)(ptabs, lengths, tok, drafts)
                targets = jnp.argmax(logits, -1).astype(jnp.int32)
                dest = jnp.where(active[:, None], ptabs, TRASH_PAGE)
                new_pool = [
                    {k: pl[k].at[dest].set(pages[li][k]) for k in pl}
                    for li, pl in enumerate(pool_layers)
                ]
                # Acceptance: longest accepted draft prefix, then budget /
                # eos truncation on the emitted stream E.
                match = drafts == targets[:, : S - 1]  # (slots, S-1)
                lead = jnp.cumprod(match.astype(jnp.int32), axis=1)
                a = lead.sum(axis=1)  # (slots,) accepted drafts
                E = targets  # (slots, S) emitted stream candidates
                if rs:
                    def verify(lg, d, tm, tk, tp_, sd, md):
                        filt = filter_logits_batched(
                            lg,
                            jnp.full((S,), tm),
                            jnp.full((S,), tk, jnp.int32),
                            jnp.full((S,), tp_),
                        )
                        return rejection_verify_row(filt, d, sd, md)

                    E_rs, a_rs = jax.vmap(verify)(
                        logits, drafts, temp, top_k, top_p, seed, made
                    )
                    is_sampled = temp > 0.0
                    a = jnp.where(is_sampled, a_rs, a)
                    E = jnp.where(is_sampled[:, None], E_rs, E)
                n0 = a + 1  # candidate emit count
                n1 = jnp.minimum(n0, budget - made)
                idx = jnp.arange(S)[None, :]
                eos_in = (E == eos[:, None]) & (idx < n1[:, None])
                any_eos = eos_in.any(axis=1)
                first_eos = jnp.argmax(eos_in, axis=1)
                n_final = jnp.where(any_eos, first_eos + 1, n1)
                n_final = jnp.where(active, n_final, 0)
                new_lengths = lengths + n_final
                new_made = made + n_final
                rows = jnp.arange(E.shape[0])
                last = jnp.clip(n_final - 1, 0, S - 1)
                new_tok = jnp.where(active, E[rows, last], tok)
                finished = active & ((new_made >= budget) | any_eos)
                valid = (idx < n_final[:, None]) & active[:, None]
                accepted = jnp.where(active, jnp.minimum(a, n_final - 1), 0)
                return (
                    new_pool, active & ~finished, new_lengths, new_tok,
                    new_made, E.T, valid.T, accepted,
                )

            return spec_fn

        def make_tree_spec(rs: bool):
            B, D = self.spec_branches, self.spec_k
            N = 1 + B * D
            S = D + 1
            # Static tree topology. Node (b, j) — branch b's depth-(j+1)
            # draft — is FED (and cache-written) at flat index 1 + b*D + j,
            # but its SEMANTIC position is length + 1 + j: write order is
            # branch-major while causal order is per-branch. The ancestor
            # mask, depth vector and parent table below encode that once,
            # as compile-time constants.
            anc = np.zeros((N, N), bool)
            anc[0, 0] = True
            par = np.zeros((B, D), np.int32)
            for b in range(B):
                for j in range(D):
                    r = 1 + b * D + j
                    anc[r, 0] = True
                    anc[r, 1 + b * D : r + 1] = True
                    par[b, j] = 0 if j == 0 else 1 + b * D + (j - 1)
            self_mask = jnp.asarray(anc)
            depth = jnp.asarray(
                np.concatenate([[0], 1 + np.tile(np.arange(D), B)]),
                jnp.int32,
            )
            par = jnp.asarray(par)

            def tree_fn(
                pool_layers, params, ptabs, active, lengths, tok, drafts,
                temp, top_k, top_p, seed, made, budget, eos,
            ):
                """One shared-draft TREE verify round. Feeds
                ``[cur_tok, branch_0 d_0..d_{D-1}, ..., branch_{B-1} ...]``
                (N = 1 + B*D tokens) per slot in ONE widened forward under
                the static ancestor ``self_mask`` — every branch verifies
                against the same committed prefix in the same program
                (SpecInfer-style tree attention), with semantic positions
                following tree depth rather than write order.

                Greedy lanes accept, per branch, the longest prefix of
                drafts matching the target's greedy outputs at their PARENT
                rows, then take the best branch (``argmax`` — first-max
                ties resolve to branch 0, the linear drafter's block, so
                accepted-per-verify dominates the linear baseline pointwise
                on the same trajectory and the emitted stream stays
                token-identical to plain greedy decode). Sampled lanes run
                ``tree_rejection_verify_row``: sequential multi-candidate
                rejection sampling over the B roots, then the PR 11 linear
                verify along the accepted branch — lossless per token.

                The accepted branch's KV block is COMPACTED in-program onto
                the canonical slot timeline (rows ``length+1+bsel*D..`` move
                to ``length+1``) before the page scatter; everything at or
                above ``length + 1 + D`` is stale junk the write-before-
                attend invariant keeps unreadable. Outputs match the linear
                verify's layout exactly (emitted streams are (S, slots)
                with S = D + 1), so round bookkeeping is shared."""

                def one(row, length, t, d, tm, tk, tp_, sd, md):
                    cache = gather_cache(pool_layers, row, length)
                    x = jnp.concatenate([t[None], d.reshape(-1)])[None]
                    positions = (length + depth)[None]
                    logits, cache = model.apply(
                        {"params": params}, x, cache=cache,
                        positions=positions, self_mask=self_mask,
                    )
                    lg = logits[0]  # (N, V)
                    targets = jnp.argmax(lg, -1).astype(jnp.int32)
                    # Greedy: per-branch leading-match runs against each
                    # node's PARENT row target, best branch wins.
                    match = d == jnp.take(targets, par)  # (B, D)
                    lead = jnp.cumprod(match.astype(jnp.int32), axis=1)
                    acc_b = lead.sum(axis=1)  # (B,)
                    bsel_g = jnp.argmax(acc_b).astype(jnp.int32)
                    rows_g = jnp.concatenate(
                        [jnp.zeros((1,), jnp.int32),
                         1 + bsel_g * D + jnp.arange(D, dtype=jnp.int32)]
                    )
                    E_g = jnp.take(targets, rows_g)  # (S,)
                    a_g = acc_b[bsel_g]
                    if rs:
                        filt = filter_logits_batched(
                            lg,
                            jnp.full((N,), tm),
                            jnp.full((N,), tk, jnp.int32),
                            jnp.full((N,), tp_),
                        )
                        E_s, a_s, bsel_s = tree_rejection_verify_row(
                            filt, d, sd, md
                        )
                        is_s = tm > 0.0
                        E = jnp.where(is_s, E_s, E_g)
                        a = jnp.where(is_s, a_s, a_g)
                        bsel = jnp.where(is_s, bsel_s, bsel_g)
                    else:
                        E, a, bsel = E_g, a_g, bsel_g

                    def compact(leaf):
                        # leaf (1, kv, S_max[, dh]); move the selected
                        # branch's D rows to the canonical offsets right
                        # after cur_tok's row (bsel = 0 is the identity).
                        starts = (0, 0, length + 1 + bsel * D)
                        starts += (0,) * (leaf.ndim - 3)
                        sizes = (leaf.shape[0], leaf.shape[1], D)
                        sizes += leaf.shape[3:]
                        blk = jax.lax.dynamic_slice(leaf, starts, sizes)
                        dst = (0, 0, length + 1) + (0,) * (leaf.ndim - 3)
                        return jax.lax.dynamic_update_slice(leaf, blk, dst)

                    pages = [
                        {k: split_pages(compact(v)[0]) for k, v in l.items()}
                        for l in cache["layers"]
                    ]
                    return pages, E, a, bsel

                pages, E, a, _bsel = jax.vmap(one)(
                    ptabs, lengths, tok, drafts, temp, top_k, top_p, seed,
                    made,
                )
                dest = jnp.where(active[:, None], ptabs, TRASH_PAGE)
                new_pool = [
                    {k: pl[k].at[dest].set(pages[li][k]) for k in pl}
                    for li, pl in enumerate(pool_layers)
                ]
                # Budget / eos truncation — verbatim the linear scheme.
                n0 = a + 1
                n1 = jnp.minimum(n0, budget - made)
                idx = jnp.arange(S)[None, :]
                eos_in = (E == eos[:, None]) & (idx < n1[:, None])
                any_eos = eos_in.any(axis=1)
                first_eos = jnp.argmax(eos_in, axis=1)
                n_final = jnp.where(any_eos, first_eos + 1, n1)
                n_final = jnp.where(active, n_final, 0)
                new_lengths = lengths + n_final
                new_made = made + n_final
                rows = jnp.arange(E.shape[0])
                last = jnp.clip(n_final - 1, 0, S - 1)
                new_tok = jnp.where(active, E[rows, last], tok)
                finished = active & ((new_made >= budget) | any_eos)
                valid = (idx < n_final[:, None]) & active[:, None]
                accepted = jnp.where(active, jnp.minimum(a, n_final - 1), 0)
                return (
                    new_pool, active & ~finished, new_lengths, new_tok,
                    new_made, E.T, valid.T, accepted,
                )

            return tree_fn

        # Compiled program set, host-selected per call. Two sampling
        # variants of prefill and step: per-row top-k/top-p needs two
        # full-vocab XLA sorts per micro-step (per-row cutoffs defeat
        # lax.top_k's static k), and an all-greedy round (THE common
        # serving mix, and what the bench's sequential baseline pays) must
        # not pay them. Plus the speculative verify program for all-greedy
        # rounds when spec_k > 0. Still a fixed set: warmup compiles every
        # member, and the compile-count assert covers the lot.
        donate = (0,)  # the pool's leaves, through every program
        prefill_of = (make_eva_prefill if self._eva
                      else make_segment_prefill if self._segmented
                      else make_prefill)
        self._prefill_greedy = self._jit_program(
            prefill_of(False), "prefill", donate
        )
        self._prefill_sampled = self._jit_program(
            prefill_of(True), "prefill", donate
        )
        self._step_greedy = self._jit_program(
            make_step(False), "step", donate
        )
        self._step_sampled = self._jit_program(
            make_step(True), "step", donate
        )
        # Tree mode (spec_branches > 1) REPLACES the linear verify
        # programs — a round is either linear or tree for an engine's
        # whole lifetime, so the compiled set stays fixed either way.
        tree_mode = self.spec_k > 0 and self.spec_branches > 1
        self._spec = (
            self._jit_program(make_spec(rs=False), "spec", (0,))
            if self.spec_k and not tree_mode
            else None
        )
        # The rejection-sampling variant serves rounds with ANY sampled
        # lane (its `where` handles mixed greedy rows); the greedy variant
        # keeps all-greedy rounds free of the filter's full-vocab sorts.
        self._spec_rs = (
            self._jit_program(make_spec(rs=True), "spec", (0,))
            if self.spec_k and not tree_mode
            else None
        )
        self._tree = (
            self._jit_program(make_tree_spec(rs=False), "tree", (0,))
            if tree_mode
            else None
        )
        self._tree_rs = (
            self._jit_program(make_tree_spec(rs=True), "tree", (0,))
            if tree_mode
            else None
        )
        self._draft = (
            self._jit_program(
                build_draft_fn(draft_cfg, self.spec_k, self.draft_window),
                "draft",
                (),
            )
            if self.draft_params is not None
            else None
        )

    # -- program / pool hooks (overridden by ShardedSlotEngine) -----------

    def _build_pool(self, cfg, max_len, kv_pages):
        return PagedKVPool(
            cfg, self.slots, max_len, self.page_size, kv_pages
        )

    def _decode_path(self) -> str:
        """How the plain decode program (``step_fn``) reaches K and V,
        fixed here once by what the engine can see of its own pool:
        ``"table"`` — attention reads the pages where they lie, through the
        page table, up to each slot's live length
        (``ops.attention.paged_decode_attention``) — when the pool's
        leaves are plain ``k`` / ``v`` rows (an int8 pool carries scale
        leaves the kernel does not read) and the kernel takes their shape
        (``ops.attention.paged_decode_fits``: a page a whole number of the
        leaf dtype's sublane tiles, 16 rows of bf16 or 8 of f32, the head
        size a whole number of lanes); ``"gather"`` — every slot's logical
        cache is gathered from its table row — everywhere else. The
        prefill, chunk and verify programs gather on either path; how a
        prefill or chunk program then attends its gathered row is
        ``prefill_path``'s to say, and the verify programs attend it
        densely."""
        leaves = self.pool.layers[0]
        if self._eva or self._segmented or self._mla:
            # Always through the table: the composed row IS the cache (EVA);
            # the slot's state is one row a slot beside it, and the model is
            # told which lanes are live (a segmented config); a gathered
            # latent row would copy each slot's whole row a round.
            # Where the kernel does not take the leaves the sublayer sums
            # the same rows in jax.numpy (models/transformer.py).
            return "table"
        if set(leaves) == {"k", "v"} and paged_decode_fits(leaves["k"]):
            return "table"
        return "gather"

    def _prefill_path(self) -> str:
        """How the prefill and chunk programs (``prefill_fn``) attend the
        row they gather, fixed here once like ``decode_path``: ``"flash"``
        — the chunk's query rows attend the live keys by blocks at the
        traced offset (``ops.attention.chunk_flash_attention``) — when the
        pool's leaves are plain ``k`` / ``v`` rows and the kernel takes the
        shapes (``ops.attention.chunk_flash_fits``: the head a whole number
        of lanes, ``max_len`` and every ``prefill_buckets`` width a whole
        number of the leaf dtype's sublane tiles); ``"dense"`` — scores
        over all ``max_len`` positions, masked — everywhere else: int8
        pages, the CPU smoke shapes with heads of 8-32, and the EVA and CCA
        prefill programs, whose sublayers have dense sites of their own. A
        segment program's gathered row is a bucket's worth of trash pages
        longer than ``max_len``: those lengths must fit too. ``"latent"``:
        latent attention's chunk attends its gathered latent rows by blocks
        up to its last row, expanded or absorbed by its width
        (``models/transformer.mla_prefill_form``)."""
        if self._mla:
            return "latent"
        k = self._k_leaf()
        if self._eva or self._cca or k is None or any(
                "k" in l and set(l) != {"k", "v"} for l in self.pool.layers):
            return "dense"
        ps = self.page_size
        rows = [self.max_len, *self.prefill_buckets]
        if self._segmented:
            rows += [self.max_len + -(-w // ps) * ps
                     for w in self.prefill_buckets]
        fits = chunk_flash_fits(k.dtype, k.shape[3], rows)
        return "flash" if fits else "dense"

    def _k_leaf(self):
        """The ``k`` leaf of the first layer that holds pages (every layer
        but a layer_pattern config's ``M`` and ``E`` ones), latent
        attention's ``lat``; None if none does."""
        return next((l.get("k", l.get("lat")) for l in self.pool.layers
                     if "k" in l or "lat" in l), None)

    def _decode_kernel_form(self) -> str | None:
        """How the paged kernel of the plain decode program forms its two
        products (``ops.attention.paged_decode_form``): ``"group"`` — the
        query group streams past latched K and V tiles — or ``"row"`` — K
        streams past one replicated query row and the VPU does the rest —
        by the group size it is handed, like ``decode_path`` a
        fact of the build and no option. ``None`` where ``step_fn`` does
        not reach the kernel: the gather path, and an EVA pool whose leaves
        the kernel does not take."""
        k = self._k_leaf()
        if (self.decode_path != "table" or k is None
                or not paged_decode_fits(k)):
            return None
        return paged_decode_form(self.cfg.num_heads // k.shape[1])

    def _kv_rows_read(self, act, lengths=None, spec=None) -> int:
        """K and V positions per layer that one micro-step of a decode
        round over the ``act`` slots at ``lengths`` reads (the coming round
        from the host registers where they are left out; ``spec``: whether
        it is a verify round). It counts for
        three cases: a verify round and the gather path (int8 pages,
        off-tile shapes, the sharded engine) read every slot's whole row;
        the table path over the plain layout reads the live pages of the
        active slots, less those a sliding window skips; the table path
        over EVA's composed rows reads the pages that hold each active
        slot's summaries and its current window up to its token (a row is
        a row to the kernel: ``summary_rows_read`` and ``window_rows_read``
        on the same span split them). ``kv.decode_read_amplification``
        divides this by the round's live tokens, which means what it says
        on the plain layout only: an EVA slot's live tokens are not its
        rows."""
        if not act.any():
            return 0
        lengths = self.lengths if lengths is None else lengths
        if spec is None:
            spec = self._spec_round(act)
        if self.decode_path == "gather" or spec:
            return self.slots * self.max_len
        ps = self.page_size
        if self._eva:
            # The composed row: summaries and window rows are one run of
            # pages to the kernel.
            sums, rows = self._eva_rows(act, lengths)
            return int((-(-(sums + rows) // ps) * ps).sum())
        n = lengths[act].astype(np.int64) + 1
        window = getattr(self.cfg, "attention_window", None)
        first = np.maximum(n - window, 0) // ps if window else 0
        return int(((-(-n // ps) - first) * ps).sum())

    def _kv_copies(self, act, lengths, spec) -> tuple[int, int] | None:
        """``(copies, pages)`` a layer that a decode round over the ``act``
        slots at ``lengths`` makes to bring in the pages it reads
        (``_kv_rows_read`` in pages). Through the paged kernel, its copy
        chain's own rule over the host's table rows
        (``ops.attention.paged_decode_copies``): one descriptor for a step
        of pages whose ids ascend by one, one a page elsewhere. A program
        that gathers takes every page by its own index: a copy a page."""
        if not act.any():
            return None
        if self.decode_kernel_form is None or spec:
            pages = self._kv_rows_read(act, lengths, spec) // self.page_size
            return pages, pages
        if self._eva:
            attend = sum(self._eva_rows(act, lengths))
        else:
            attend = lengths[act].astype(np.int64) + 1
        return paged_decode_copies(
            self.pool.page_tables[act], attend, self._k_leaf(),
            window=getattr(self.cfg, "attention_window", None))

    def _jit_program(self, fn, kind, donate):
        """Compile hook: the base engine jits on the default device; the
        sharded engine overrides this to jit the SAME program under its
        mesh with in/out shardings. ``kind`` names the fixed argument
        layout (``prefill``/``step``/``spec``/``tree``/``draft``)."""
        return jax.jit(fn, donate_argnums=donate)

    # -- slot lifecycle ---------------------------------------------------

    @property
    def free_slots(self) -> int:
        return self.pool.num_free

    @property
    def active_count(self) -> int:
        return int(self.active.sum())

    @property
    def prefilling_count(self) -> int:
        return int(self.prefilling.sum())

    @property
    def max_prompt_len(self) -> int:
        """Longest admissible prompt: ``prefill_len`` is the hard cap only
        when chunked prefill is off; with it on, any prompt that leaves
        room for one generated token fits (p + max_new <= max_len is
        validated separately)."""
        if self.prefill_chunk_tokens > 0:
            return self.max_len - 1
        return self.prefill_len

    @property
    def utilization(self) -> float:
        """Capacity in use: PAGE occupancy, the unit admission is gated
        on."""
        return self.pool.occupancy

    @property
    def prefix_hit_rate(self) -> float:
        return self.prefix.hit_rate if self.prefix is not None else 0.0

    @property
    def spec_accept_rate(self) -> float:
        prop = self.stats["spec_drafts_proposed"]
        return self.stats["spec_drafts_accepted"] / prop if prop else 0.0

    def spec_accept_rate_for(self, drafter: str) -> float:
        prop = self.stats[f"spec_drafts_proposed_{drafter}"]
        acc = self.stats[f"spec_drafts_accepted_{drafter}"]
        return acc / prop if prop else 0.0

    @property
    def spec_accept_per_verify(self) -> float:
        """Mean accepted drafts per (slot, verify-round) — the quantity
        tree speculation exists to raise: a tree round costs one widened
        forward per slot exactly like a linear round costs one narrow one,
        so accepted-per-verify is the apples-to-apples speedup axis."""
        ver = self.stats["spec_verifies"]
        return self.stats["spec_drafts_accepted"] / ver if ver else 0.0

    @property
    def kv_dtype(self) -> str:
        """Live KV-cache element format: ``'int8'`` when the pool pages
        are quantize-on-write int8 rows + f32 scales
        (``cfg.kv_cache_dtype == 'int8'``), else ``'bf16'`` — the
        compute-dtype passthrough (f32 bytes under the CPU-smoke f32
        compute dtype; the label names the serving mode, not the literal
        storage width). Travels in handoff bundle headers and /healthz so
        tiers/routers can tell formats apart."""
        quant = getattr(self.cfg, "kv_cache_dtype", None)
        return "int8" if quant == "int8" else "bf16"

    @property
    def kv_bytes_per_token(self) -> float:
        """KV-cache bytes one token position costs across all layers in
        the live pool format (int8 rows carry their f32 scale overhead) —
        the byte-diet gauge ``bench_serving`` ratios int8 against bf16."""
        return self.pool.bytes_per_token

    def acquire_slot(self) -> int | None:
        return self.pool.alloc()

    def release(self, slot: int) -> None:
        if self.active[slot]:
            # A slot that is still decoding (a cancel, a deadline): a round
            # in flight carries it on, so the host's word has to win at the
            # merge. A slot that finished is masked on the device already.
            self._touched[slot] = True
        self.active[slot] = False
        if self.prefilling[slot]:
            self.prefilling[slot] = False
            self._pf.pop(slot, None)
            try:
                self._pf_queue.remove(slot)
            except ValueError:
                pass
        self._eva_total[slot] = 0
        self.pool.free(slot)

    def pause(self, slot: int) -> None:
        """Stop decoding ``slot`` and keep its registers and pages (a slot
        parked for a handoff); :meth:`resume` takes it up where it stood."""
        self._touched[slot] = True
        self.active[slot] = False

    def resume(self, slot: int) -> None:
        self._touched[slot] = True
        self.active[slot] = True

    def start(
        self,
        slot: int,
        prompt,
        *,
        max_new_tokens: int,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 0.0,
        seed: int = 0,
        eos_id: int | None = None,
    ) -> tuple[int | None, bool]:
        """Prefill ``prompt`` into ``slot`` and sample its first token.

        Returns ``(first_token, finished)``; a request that is already done
        after one token (budget 1, or the first token is its eos) comes
        back ``finished=True`` and the caller releases the slot. Raises
        :class:`InsufficientPages` (slot untouched, no references leaked)
        when the pool cannot back the request even after evicting
        prefix-cache entries.

        When the post-adoption tail exceeds the chunk width (possible only
        with chunked prefill enabled), no forward runs here: the slot
        enters the PREFILLING phase, ``(None, False)`` is returned, and
        the first token surfaces from a later :meth:`step` once the final
        chunk lands (its row precedes that round's decode rows)."""
        prompt = np.asarray(prompt, np.int32).ravel()
        # The spans of this module sit inside the methods they time, never
        # in a wrapper around them: every Python frame above a jitted
        # program's first call makes its tracing slower (warm-up is
        # measurably longer three frames deeper).
        p = int(prompt.size)
        with _trace.span("engine.start", flight=False, prompt_len=p,
                         path=self.prefill_path) as sp:
            matched0 = self.stats["prefix_tokens_matched"]
            if p < 1:
                raise ValueError("prompt must contain at least one token")
            if p > self.max_prompt_len:
                raise ValueError(
                    f"prompt length {p} > engine prefill_len {self.prefill_len}"
                    if self.max_prompt_len == self.prefill_len
                    else f"prompt length {p} > engine max prompt "
                         f"{self.max_prompt_len}"
                )
            if max_new_tokens < 1:
                raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
            if p + max_new_tokens > self.max_len:
                raise ValueError(
                    f"prompt {p} + {max_new_tokens} new > engine max_len "
                    f"{self.max_len}"
                )
            sampled = temperature > 0.0
            prefill = self._prefill_sampled if sampled else self._prefill_greedy
            sargs = (
                np.float32(temperature), np.int32(top_k), np.float32(top_p),
                np.uint32(seed),
            )
            eos = -1 if eos_id is None else int(eos_id)
            first = self._bind_and_prefill(
                slot, prompt, p, max_new_tokens, prefill, sargs, sampled)
            # Registers shared by both outcomes (immediate first token vs
            # PREFILLING): sampling params and limits are fixed at admission.
            self._touched[slot] = True
            self.temp[slot] = temperature
            self.top_k[slot] = top_k
            self.top_p[slot] = top_p
            self.seed[slot] = np.uint32(seed & 0xFFFFFFFF)
            self.budget[slot] = max_new_tokens
            self.eos[slot] = eos
            if first is None:
                # Chunked path scheduled by _bind_and_prefill; pages are all
                # bound, chunks spend across subsequent step() calls.
                self.active[slot] = False
                self.lengths[slot] = 0
                self.made[slot] = 0
                if self.spec_k:
                    self.history[slot, :p] = prompt
                    self.hist_len[slot] = p
                if self.sentinel is not None:
                    self.sentinel.poll(self.compile_count())
                sp.note(matched=self.stats["prefix_tokens_matched"] - matched0,
                        chunks=len(self._pf[slot]["chunks"]))
                return None, False
            first = int(first)
            finished = max_new_tokens == 1 or first == eos
            self.active[slot] = not finished
            self.lengths[slot] = p
            self.cur_tok[slot] = first
            self.made[slot] = 1
            if self.spec_k:
                self.history[slot, :p] = prompt
                self.history[slot, p] = first
                self.hist_len[slot] = p + 1
            if self.sentinel is not None:
                self.sentinel.poll(self.compile_count())
            sp.note(matched=self.stats["prefix_tokens_matched"] - matched0,
                    chunks=0, **self._mla_note)
            return first, finished

    def _bind_and_prefill(self, slot, prompt, p, max_new, prefill, sargs,
                          sampled):
        """Page allocation + prefix adoption + tail prefill for one slot.
        Returns the first token, or ``None`` when the tail exceeds every
        bucket and a chunked-prefill plan was scheduled instead."""
        if self._eva:
            return self._start_eva(slot, prompt, p, max_new, sargs, sampled)
        if self._segmented:
            return self._start_segments(slot, prompt, p, max_new, sargs,
                                        sampled)
        pool, ps = self.pool, self.page_size
        n_pages = pool.pages_needed(p, max_new)
        # Adoption cap: the tail must keep >= 1 real token (the first-
        # token logits come from position p-1). The per-bucket clamp below
        # additionally keeps the tail write under max_len.
        cap = (p - 1) // ps
        matched = self.prefix.match(prompt, cap) if self.prefix else []
        # Pick the narrowest compiled prefill width whose bucket holds the
        # post-adoption tail. Per bucket, adoption is clamped so the tail
        # write at offset m0 fits below max_len (dynamic_update_slice
        # would CLAMP the start down and corrupt adopted rows otherwise);
        # with chunking off the largest bucket (prefill_len, clamp
        # included) always fits since start() validated p <= prefill_len.
        # Adopted pages beyond the clamp are returned — their content is
        # simply recomputed by the (still narrower) tail forward. A tail
        # wider than every bucket (a long prompt under chunked prefill)
        # falls through to the chunk planner.
        m_pages = 0
        fits = False
        for width in self.prefill_buckets:
            m_pages = min(len(matched), (self.max_len - width) // ps)
            if p - m_pages * ps <= width:
                fits = True
                break
        if not fits:
            return self._start_chunked(slot, prompt, p, max_new, sargs,
                                       sampled, matched)
        for pid in matched[m_pages:]:
            pool.decref(pid)
        matched = matched[:m_pages]
        own = self._alloc_pages(n_pages - len(matched))
        if own is None:
            for pid in matched:
                pool.decref(pid)
            raise InsufficientPages(
                f"need {n_pages - len(matched)} pages, "
                f"{pool.pages_free} free (slot {slot}, prompt {p} + "
                f"{max_new} new @ page_size {ps})"
            )
        page_ids = matched + own
        pool.bind(slot, page_ids)
        m0 = len(matched) * ps
        # The forward consumes only the TAIL — positions below m0 are
        # covered by adopted pages; the padded tail lands at cache offset
        # m0 inside the program.
        padded = np.zeros((1, width), np.int32)
        padded[0, : p - m0] = prompt[m0:]
        row = np.array(pool.page_tables[slot])  # defensive copy for the jit
        new_pool, first = prefill(
            pool.layers, self.params, padded, np.int32(p), np.int32(m0),
            row, *sargs,
        )
        pool.layers = new_pool
        self._mla_note = self._mla_prefill_note(m0, width)
        if self.prefix is not None:
            self.prefix.record_lookup(m0, p)
            self.prefix.insert(prompt, page_ids)
            self.stats["prefix_tokens_matched"] = self.prefix.tokens_matched
            self.stats["prefix_tokens_total"] = self.prefix.tokens_looked_up
        return first

    def _start_chunked(self, slot, prompt, p, max_new, sargs, sampled,
                       matched):
        """Bind every page up front and plan the chunk schedule; no
        forward runs here. The plan is a list of ``(offset, width,
        is_final)`` bucket-program calls: full-chunk-width intermediates
        (sampled token discarded), then ONE suffix-aligned final chunk —
        its fed window ends at position ``p-1`` so the first-token logits
        come from the true last prompt position, with no padding anywhere.

        Adoption is capped so the post-adoption remainder strictly
        exceeds the chunk width: that forces >= 1 intermediate chunk,
        which keeps the final chunk's window start ``p - w`` strictly
        above the adopted boundary — the final forward only ever REwrites
        the slot's own pages (overlap recompute is deterministic and
        write-before-attend makes it safe), never a shared prefix page."""
        pool, ps, c = self.pool, self.page_size, self.prefill_chunk_tokens
        n_pages = pool.pages_needed(p, max_new)
        a = min(len(matched), max(0, (p - c - 1) // ps))
        for pid in matched[a:]:
            pool.decref(pid)
        matched = matched[:a]
        own = self._alloc_pages(n_pages - len(matched))
        if own is None:
            for pid in matched:
                pool.decref(pid)
            raise InsufficientPages(
                f"need {n_pages - len(matched)} pages, "
                f"{pool.pages_free} free (slot {slot}, prompt {p} + "
                f"{max_new} new @ page_size {ps}, chunked)"
            )
        page_ids = matched + own
        pool.bind(slot, page_ids)
        m0 = len(matched) * ps
        chunks = []
        m = m0
        while p - m > c:
            chunks.append((m, c, False))
            m += c
        r = p - m  # 1 <= r <= c: the suffix the final chunk must cover
        w = next(b for b in self.prefill_buckets if b >= r)
        chunks.append((p - w, w, True))
        self._pf[slot] = {
            "slot": slot, "prompt": prompt, "p": p, "chunks": chunks,
            "idx": 0, "sampled": sampled, "sargs": sargs,
            "page_ids": page_ids, "m0": m0,
        }
        self.prefilling[slot] = True
        self._pf_queue.append(slot)
        if self.prefix is not None:
            self.prefix.record_lookup(m0, p)
            self.stats["prefix_tokens_matched"] = self.prefix.tokens_matched
            self.stats["prefix_tokens_total"] = self.prefix.tokens_looked_up
        return None

    def _mla_prefill_note(self, offset: int, width: int) -> dict:
        """What a latent-attention prefill of ``width`` rows at ``offset``
        notes on its span: the form its shapes take
        (``models/transformer.mla_prefill_form``) and the latent rows its
        attention reads, a layer (nothing on any other config)."""
        if not self._mla:
            return {}
        return {"mla_form": mla_prefill_form(self.cfg, width, self.max_len),
                "latent_rows_attended": int(offset + width)}

    def _alloc_pages(self, n: int):
        """``n`` pages from the pool, after asking the prefix cache to give
        up entries where the free list is short; None if still short."""
        own = self.pool.alloc_pages(n)
        if own is None and self.prefix is not None:
            self.prefix.evict_for(n)
            own = self.pool.alloc_pages(n)
        return own

    def _start_eva(self, slot, prompt, p, max_new, sargs, sampled):
        """Admission of an EVA slot: reserve the most pages the request
        will hold at once, adopt what the prefix cache has of the prompt
        (whole windows as summary pages, then full K/V pages of the window
        after them; the tail keeps at least one token), bind the window
        the prefill starts in and, if the request goes past it, its forming
        summary pages, and plan the prefill as SEGMENTS: at most a chunk
        wide, none across a window's end. One segment runs here and its
        token is returned; more are spent by :meth:`step` like any chunk
        plan (``None`` is returned)."""
        pool, ps, w = self.pool, self.page_size, self.pool.window
        total = p + max_new
        most = pool.pages_needed(p, max_new)
        if not pool.reserve(slot, most):
            raise InsufficientPages(
                f"slot {slot}: {most} pages at the most for prompt {p} + "
                f"{max_new} new would pass the pool's "
                f"{pool.pages_allocatable} beside what is reserved")
        sums, wins = (self.prefix.match_eva(prompt, p - 1)
                      if self.prefix is not None else ([], []))
        done = len(sums) // pool.sum_pages
        m0 = done * w + len(wins) * ps
        n_win = -(-(min((done + 1) * w, total) - done * w) // ps) - len(wins)
        forming = pool.sum_pages if total > (done + 1) * w else 0
        own = self._alloc_pages(n_win + forming)
        if own is None:
            for pid in sums + wins:
                pool.decref(pid)
            pool.reserved[slot] = 0
            raise InsufficientPages(
                f"need {n_win + forming} pages, {pool.pages_free} free "
                f"(slot {slot}, prompt {p} + {max_new} new, EVA)")
        pool.bind_eva(slot, sums, wins + own[:n_win], own[n_win:])
        self._eva_total[slot] = total
        self.stats["eva_summary_pages_adopted"] += len(sums)
        chunks, m = [], m0
        while m < p:
            r = min(self.prefill_chunk_tokens, (m // w + 1) * w - m, p - m)
            chunks.append((m, r, m + r == p))
            m += r
        st = {
            "slot": slot, "prompt": prompt, "p": p, "chunks": chunks,
            "idx": 0, "sampled": sampled, "sargs": sargs, "m0": m0,
        }
        if self.prefix is not None:
            self.prefix.record_lookup(m0, p)
            self.stats["prefix_tokens_matched"] = self.prefix.tokens_matched
            self.stats["prefix_tokens_total"] = self.prefix.tokens_looked_up
        if len(chunks) == 1:
            return self._run_chunk(st, *chunks[0])
        self._pf[slot] = st
        self.prefilling[slot] = True
        self._pf_queue.append(slot)
        return None

    def _start_segments(self, slot, prompt, p, max_new, sargs, sampled):
        """Admission of a slot of a segmented config (slot state, routed
        experts): every page bound up front as in the
        plain layout (nothing adopted: the engine has no prefix cache), the
        prefill planned as SEGMENTS of at most a chunk that never overlap
        (a recomputed position would find the slot's state ahead of it,
        and would count twice at the experts), the last padded to its bucket. One segment runs here and its
        token is returned; more are spent by :meth:`step` like any chunk
        plan (``None`` is returned)."""
        pool = self.pool
        n_pages = pool.pages_needed(p, max_new)
        own = self._alloc_pages(n_pages)
        if own is None:
            raise InsufficientPages(
                f"need {n_pages} pages, {pool.pages_free} free (slot {slot}, "
                f"prompt {p} + {max_new} new @ page_size {self.page_size})")
        pool.bind(slot, own)
        c = (self.prefill_chunk_tokens if self.prefill_chunk_tokens > 0
             else self.prefill_len)
        chunks = [(m, min(c, p - m), m + c >= p) for m in range(0, p, c)]
        st = {
            "slot": slot, "prompt": prompt, "p": p, "chunks": chunks,
            "idx": 0, "sampled": sampled, "sargs": sargs,
        }
        if len(chunks) == 1:
            return self._run_chunk(st, *chunks[0])
        self._pf[slot] = st
        self.prefilling[slot] = True
        self._pf_queue.append(slot)
        return None

    def _run_segment(self, st, m, r, final):
        """One prefill segment of a segmented config's slot: ``r`` real
        tokens at position ``m``, padded to the narrowest bucket."""
        pool, slot = self.pool, st["slot"]
        width = next(b for b in self.prefill_buckets if b >= r)
        toks = np.zeros((1, width), np.int32)
        toks[0, :r] = st["prompt"][m : m + r]
        prefill = (self._prefill_sampled if final and st["sampled"]
                   else self._prefill_greedy)
        new_pool, first = prefill(
            pool.layers, self.params, toks, np.int32(r), np.int32(m),
            np.array(pool.page_tables[slot]), np.int32(slot), *st["sargs"],
        )
        pool.layers = new_pool
        if self._ssm:
            self.stats["ssm_tokens_scanned"] += r
        return int(first) if final else None

    def _run_eva_segment(self, st, m, r, final):
        """One prefill segment of an EVA slot (``r`` real tokens at
        absolute ``m``, padded to the narrowest bucket), then what its end
        brings: a window that the prompt fills is indexed in the prefix
        cache by its summary pages and rolled; at the prompt's end the full
        K/V pages of the window it ends in are indexed."""
        pool, w, slot = self.pool, self.pool.window, st["slot"]
        prompt = st["prompt"]
        width = next(b for b in self.prefill_buckets if b >= r)
        toks = np.zeros((1, width), np.int32)
        toks[0, :r] = prompt[m : m + r]
        prefill = (self._prefill_sampled if final and st["sampled"]
                   else self._prefill_greedy)
        new_pool, first = prefill(
            pool.layers, self.params, toks, np.int32(r), np.int32(m),
            np.array(pool.page_tables[slot]), *st["sargs"],
        )
        pool.layers = new_pool
        end = m + r
        if end % w == 0:
            if self.prefix is not None:
                self.prefix.insert_summaries(
                    prompt, end // w - 1, pool.forming_row(slot))
            self._roll_window(slot, end)
        elif final and self.prefix is not None:
            self.prefix.insert(prompt, pool.window_row(slot),
                               first_page=end // w * w // self.page_size)
        return int(first) if final else None

    def _roll_window(self, slot: int, length: int) -> None:
        """``slot`` has filled a window (``length`` is a whole number of
        them): release its pages, move its summaries into the table and
        bind the next window's (``PagedKVPool.roll_window``)."""
        pool = self.pool
        left = int(self._eva_total[slot]) - length
        if self.active[slot]:
            self._touched[slot] = True  # its row of the page table changes
        with _trace.span("engine.window_roll", flight=False) as sp:
            released = pool.roll_window(
                slot, -(-min(pool.window, left) // self.page_size),
                forming=left > pool.window,
                evict=self.prefix.evict_for if self.prefix is not None
                else None,
            )
            sp.note(pages_released=released)
        self.stats["eva_windows_rolled"] += 1
        self.stats["eva_window_pages_released"] += released

    def _eva_rows(self, act, lengths):
        """(summary rows, window rows) that the next token of each ``act``
        slot at ``lengths`` attends, per layer: 'window/chunk' summaries
        for every finished window, and the current window up to the token
        itself."""
        n = lengths[act].astype(np.int64)
        w = self.pool.window
        return (n // w * (self.pool.sum_pages * self.page_size), n % w + 1)

    def _advance_prefill(self):
        """Spend up to ``prefill_chunk_tokens`` of prefill this iteration
        (always >= 1 chunk when any slot is PREFILLING — forward progress
        is unconditional), round-robin across prefilling slots. Returns
        ``(events, spent)`` where events are ``(slot, first_token,
        finished)`` for slots whose FINAL chunk landed this call."""
        events = []
        spent = 0
        chunks_run = 0
        budget = self.prefill_chunk_tokens
        # A round in flight that this call reads was queued before the
        # chunk plan was known: a chunk behind it would still be running
        # when the call returns, and the next call would queue a second one
        # behind it (step(): chunks never run ahead of the host). The plan
        # waits this one call; the next finds no round in flight.
        hold = (bool(self._pf_queue) and self._flight is not None
                and self.active.any())
        while self._pf_queue and not hold:
            slot = self._pf_queue[0]
            st = self._pf[slot]
            m, w, final = st["chunks"][st["idx"]]
            if spent and spent + w > budget:
                break
            first = self._run_chunk(st, m, w, final)
            spent += w
            chunks_run += 1
            st["idx"] += 1
            if final:
                self._pf_queue.popleft()
                del self._pf[slot]
                self.prefilling[slot] = False
                prompt, p = st["prompt"], st["p"]
                eos = int(self.eos[slot])
                finished = int(self.budget[slot]) == 1 or first == eos
                self._touched[slot] = True
                self.active[slot] = not finished
                self.lengths[slot] = p
                self.cur_tok[slot] = first
                self.made[slot] = 1
                if self.spec_k:
                    self.history[slot, p] = first
                    self.hist_len[slot] = p + 1
                if self.prefix is not None and not self._eva:
                    # Pages only become adoptable once every position is
                    # filled — insert at completion, not at start(). (An
                    # EVA segment indexes its own, _run_eva_segment.)
                    self.prefix.insert(prompt, st["page_ids"])
                events.append((slot, first, finished))
            else:
                self._pf_queue.rotate(-1)
        self.stats["prefill_chunks"] += chunks_run
        self.stats["prefill_tokens_last_iter"] = spent
        return events, spent

    def _run_chunk(self, st, m, w, final):
        """One bucket-program call of the chunk plan for one slot: ``w``
        REAL tokens at offset ``m`` (cache resumes at len = m; the
        program's scatter-back writes every page in the row, where pages
        below the chunk round-trip their gathered values). Intermediate
        chunks discard the sampled token; the final chunk's is the
        request's first token."""
        pool = self.pool
        prompt, p = st["prompt"], st["p"]
        # Only the final chunk blocks (on its token); the others return as
        # soon as the program is queued.
        with _trace.span("engine.prefill_chunk", flight=False, offset=m,
                         width=w, final=final, path=self.prefill_path) as sp:
            if self._eva:
                return self._run_eva_segment(st, m, w, final)
            if self._segmented:
                if self._ssm:
                    # SSD blocks the segment's bucket scans, over the layers.
                    width = next(b for b in self.prefill_buckets if b >= w)
                    sp.note(ssm_blocks=-(-width // self.cfg.ssm_block)
                            * self.cfg.layer_pattern.count("M"))
                return self._run_segment(st, m, w, final)
            sp.note(**self._mla_prefill_note(m, w))
            toks = np.ascontiguousarray(prompt[m : m + w][None])
            row = np.array(pool.page_tables[st["slot"]])
            prefill = (
                self._prefill_sampled
                if final and st["sampled"]
                else self._prefill_greedy
            )
            length = np.int32(p if final else m + w)
            new_pool, first = prefill(
                pool.layers, self.params, toks, length, np.int32(m), row,
                *st["sargs"],
            )
            pool.layers = new_pool
            return int(first) if final else None

    def step(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """One batch round over every slot.

        Returns ``(tokens (k, slots) int32, valid (k, slots) bool,
        done (slots,) bool)`` — ``k`` is 1 for plain rounds and
        ``spec_k + 1`` for speculative rounds (callers iterate rows under
        the valid mask, so the burst size is opaque to them). ``done``
        marks slots that finished during this round — the caller collects
        their output and ``release``s them, which is what lets the NEXT
        round admit replacements (iteration-level batching).

        With chunked prefill in flight, each call first spends one
        iteration's prefill budget (PREFILLING slots advance one or more
        chunks), then runs the normal decode round over the ACTIVE slots
        — long prefills never stall co-resident decodes. A slot whose
        final chunk lands this call contributes its first token as one
        extra LEADING row and joins the next round queued.

        **Run-ahead of depth one.** The call that returns round n's tokens
        first QUEUES round n+1 from round n's output registers, which are
        still on the device (``active, lengths, tok, made``; the sampling
        parameters, budgets, eos ids and the page table are device copies
        of the last upload), then waits for round n and reads it: the
        device runs n+1 while the host reads back, delivers, completes and
        comes in again. Never two rounds ahead of the host's reading, so
        an admission's prefill waits behind one round at the most. The
        host's own changes (:meth:`start`, :meth:`release` of a slot still
        decoding, a final prefill chunk, :meth:`import_slot`,
        :meth:`pause` / :meth:`resume`, a window's roll) mark the slot
        ``_touched``: with a slot touched nothing is queued early; the
        round in flight is read, touched slots keep the HOST's registers
        and yield nothing from that round, the rest take the device's, and
        the next round goes out from the merged registers, uploaded once,
        in the same call. Four more things the engine sees in its own
        state keep a round from being queued early: it may be a verify
        round (``spec_k > 0``: the drafts need round n's tokens), an EVA
        slot fills its window in round n (the roll precedes n+1), no slot
        outlives round n's budgets, or a slot is PREFILLING. While chunks
        are being spent every call is the synchronous one it was (chunk,
        round, wait, read): a call that returned before its chunk had run
        would let the next call queue a second chunk behind it, and an
        admission's prefill would wait behind both; so a chunk plan also
        waits one call where a round queued before it is still to be read
        (:meth:`_advance_prefill`). A slot released while a round in
        flight still carries it (a cancel) may get one more K/V row
        written: into a page of its own at or above its prompt's end,
        which the prefix cache never holds, and BEFORE any program of the
        page's next owner, which is queued behind it and writes every row
        it will attend (the module's overwrite invariant)."""
        if not self.active.any() and not self.prefilling.any():
            raise RuntimeError("step() with no active slots")
        with _trace.span("engine.round", flight=False) as sp:
            chunks0 = self.stats["prefill_chunks"]
            pre_events, _ = self._advance_prefill()
            # A slot whose final chunk just landed is among the active.
            if self.active.any():
                toks, valid, done = self._decode_round()
                rnd = self._returned
                act, lengths = rnd.was_active, rnd.lengths
            else:
                rnd = None
                act, lengths = self.active, self.lengths
                toks = np.zeros((0, self.slots), np.int32)
                valid = np.zeros((0, self.slots), bool)
                done = np.zeros(self.slots, bool)
                if self.sentinel is not None:
                    self.sentinel.poll(self.compile_count())
            # What the decode round whose tokens this call returns worked
            # on: the registers it ran with, not those the host holds now.
            sp.note(
                active=int(act.sum()),
                live_tokens=int(lengths[act].sum()),
                chunks_run=self.stats["prefill_chunks"] - chunks0,
                kv_rows_read=self._kv_rows_read(
                    act, lengths, rnd is not None and rnd.spec),
                ahead=rnd is not None and rnd.ahead,
            )
            chain = self._kv_copies(
                act, lengths, rnd is not None and rnd.spec)
            if chain is not None:
                sp.note(kv_copies=chain[0], kv_pages=chain[1])
                self.stats["kv_copies"] += chain[0]
                self.stats["kv_pages_copied"] += chain[1]
            # Counted from the registers the round ran with: no sync.
            writes = int(act.sum()) if rnd is not None else 0
            sp.note(kv_row_writes=writes)
            self.stats["kv_row_writes"] += writes
            if self._eva:
                sums, rows = self._eva_rows(act, lengths)
                fills = 0 if rnd is None else int(
                    (act & ((lengths + 1) % self.page_size == 0)).sum())
                sp.note(summary_rows_read=int(sums.sum()),
                        window_rows_read=int(rows.sum()),
                        eva_summary_writes=fills)
                self.stats["eva_summary_writes"] += fills
            if rnd is not None and rnd.moe is not None:
                # Of ``experts_total`` (layer, expert) pairs held here.
                sp.note(experts_touched=int(rnd.moe[0]),
                        expert_tokens_max=int(rnd.moe[1]),
                        experts_total=(self.cfg.expert_layers
                                       * len(self.cfg.held)))
            if self._ssm:
                # Live lanes whose recurrent state the round advanced.
                sp.note(ssm_lanes=int(act.sum()) if rnd is not None else 0)
            if self._mla:
                # Positions the latent kernel read, over slots and layers.
                sp.note(latent_rows=0 if rnd is None else self.cfg.num_layers
                        * int((lengths[act].astype(np.int64) + 1).sum()))
            if pre_events:
                row_t = np.zeros((1, self.slots), np.int32)
                row_v = np.zeros((1, self.slots), bool)
                for slot, first, finished in pre_events:
                    row_t[0, slot] = first
                    row_v[0, slot] = True
                    if finished:
                        done[slot] = True
                toks = np.concatenate([row_t, toks])
                valid = np.concatenate([row_v, valid])
            return toks, valid, done

    def _decode_round(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Read one decode round, with the next one queued behind it
        wherever the host has nothing to say before it (run-ahead of depth
        one; the order and what keeps a round from being queued early are in
        :meth:`step`)."""
        rnd = self._flight
        if rnd is None:
            rnd = self._dispatch()
        nxt = None
        if not self._touched.any() and self._host_silent():
            nxt = self._dispatch(rnd)
        result = self._finish_round(rnd, nxt)
        if self._eva:
            # A slot whose new length opens a window rolls before the next
            # round: its table still ends in the window it has filled.
            due = self.active & (
                self.lengths // self.pool.window > self.pool.windows_done)
            for slot in np.nonzero(due)[0]:
                self._roll_window(int(slot), int(self.lengths[slot]))
        if rnd.spec:
            self._count_spec(rnd.was_active, rnd.out[6], rnd.any_sampled)
        if (nxt is None and self.active.any() and not self._drafts_on_host
                and not self.prefilling.any()):
            # The host had its say (a merge, a roll): the next round goes
            # out from its registers here, and not a whole call later.
            nxt = self._dispatch()
        self._flight, self._returned = nxt, rnd
        return result

    @property
    def _drafts_on_host(self) -> bool:
        """A verify round's drafts are made from the tokens of the round
        before it, on the host: such an engine queues nothing early."""
        return bool(self.spec_k) and not self._force_plain

    def _host_silent(self) -> bool:
        """Whether the round after the one in flight needs nothing from the
        host, by the registers the one in flight ran with (no slot is
        touched, so they are the host's): it is no verify round, no slot
        is PREFILLING (the next call's chunk has to precede it), a slot
        stays active after the one in flight (a budget's end is known here,
        an eos is not), and no EVA slot fills its window in it (the roll
        has to precede the round that writes into the next window)."""
        if self._drafts_on_host or self.prefilling.any():
            return False
        act = self.active
        if not (act & (self.made + 1 < self.budget)).any():
            return False
        return not (self._eva and (
            (self.lengths[act] + 1) % self.pool.window == 0).any())

    def _dispatch(self, prev: _Round | None = None) -> _Round:
        """Queue one decode round and return its record. Without ``prev``
        from the host's registers, copied (the host writes them in place
        while the device may still be reading) and uploaded once; with
        ``prev``, a round not yet read, from its output registers and the
        device copies of the last upload: nothing crosses from the host."""
        with _trace.span("engine.dispatch", flight=False):
            if prev is None:
                was_active, lengths = self.active.copy(), self.lengths.copy()
                self._touched[:] = False
            else:
                was_active = lengths = None
            # The sampled program handles greedy rows correctly (via
            # `where`), so a mixed batch runs sampled; only an all-greedy
            # batch takes the sort-free fast path (and, when enabled, the
            # speculative one). Queued ahead, the batch is at most the one
            # `prev` ran with.
            any_sampled = bool((self.temp[self.active] > 0.0).any())
            spec = prev is None and self._spec_round(was_active)
            if spec:
                layers, *out = self._spec_dispatch(any_sampled)
            else:
                self.stats["plain_rounds"] += 1
                if prev is None:
                    consts = (self.temp, self.top_k, self.top_p, self.seed,
                              self.budget, self.eos, self.pool.page_tables)
                    active, length, tok, made, *self._dev_consts = self._put(
                        (was_active, lengths, self.cur_tok.copy(),
                         self.made.copy(), *(np.array(c) for c in consts)))
                else:
                    self.stats["rounds_ahead"] += 1
                    active, length, tok, made = prev.out[:4]
                temp, top_k, top_p, seed, budget, eos, ptabs = (
                    self._dev_consts)
                step = self._step_sampled if any_sampled else self._step_greedy
                layers, *out = step(
                    self.pool.layers, self.params, ptabs, active, length,
                    tok, temp, top_k, top_p, seed, made, budget, eos,
                )
            # The pool is donated through: whatever is queued next (a
            # prefill chunk, the next round) takes this round's output.
            self.pool.layers = layers
        return _Round(tuple(out), was_active, lengths, prev is not None,
                      spec, any_sampled)

    def _put(self, host: tuple) -> tuple:
        """Host registers to the device arrays the step programs take
        (the sharded engine places them replicated over its mesh)."""
        return jax.device_put(host)

    def _spec_round(self, act) -> bool:
        """Whether the coming round over the ``act`` slots is a verify
        round. Verify writes the whole fed block above each slot's length
        (spec_k+1 linear, 1+B*spec_k tree); a slot within that of max_len
        would clamp the write — fall back to plain rounds for that (rare,
        end-of-window) round."""
        return bool(
            self.spec_k
            and not self._force_plain
            and (self.lengths[act] + self._spec_write <= self.max_len).all()
        )

    def _spec_dispatch(self, any_sampled: bool = False):
        """Draft on the host and queue the verify program; returns its
        (still in flight) outputs, the accepted counts last."""
        if self.spec_branches > 1:
            drafts = self._propose_tree_drafts()
            spec = self._tree_rs if any_sampled else self._tree
        else:
            drafts = self._propose_drafts()
            spec = self._spec_rs if any_sampled else self._spec
        return spec(
            self.pool.layers, self.params, self.pool.page_tables,
            self.active, self.lengths, self.cur_tok, drafts, self.temp,
            self.top_k, self.top_p, self.seed, self.made, self.budget,
            self.eos,
        )

    def _count_spec(self, was_active, accepted, any_sampled: bool) -> None:
        """A verify round's acceptance into ``stats``; read after
        ``_finish_round`` so that the round's one blocking read stays in
        ``engine.wait``."""
        n_act = int(was_active.sum())
        # "Proposed" counts the acceptable path budget (spec_k per slot)
        # in BOTH modes, so accept-rate stays comparable between linear
        # and tree rounds; the tree's extra branches only buy a better
        # chance of a long path, never more accepted tokens per verify.
        proposed = n_act * self.spec_k
        acc_arr = np.asarray(accepted)
        accepted_n = int(acc_arr.sum())
        self.accept_samples.extend(int(x) for x in acc_arr[was_active])
        self.stats["spec_rounds"] += 1
        self.stats["spec_verifies"] += n_act
        if any_sampled:
            self.stats["spec_rounds_sampled"] += 1
        self.stats["spec_drafts_proposed"] += proposed
        self.stats["spec_drafts_accepted"] += accepted_n
        self.stats[f"spec_drafts_proposed_{self.drafter}"] += proposed
        self.stats[f"spec_drafts_accepted_{self.drafter}"] += accepted_n

    def _propose_drafts(self) -> np.ndarray:
        """(slots, spec_k) draft tokens for the active lanes: the learned
        draft model when loaded (one jitted call over every lane — the
        cur_tok is the LAST history entry, so the draft's first output is
        its prediction for the token after it), else the host n-gram
        prompt-lookup fallback. Inactive lanes draft from a length-1 dummy
        window; the verify masks them out."""
        drafts = np.zeros((self.slots, self.spec_k), np.int32)
        if self._draft is not None:
            W = self.draft_window
            toks = np.zeros((self.slots, W), np.int32)
            lens = np.ones(self.slots, np.int32)
            pos0 = np.zeros(self.slots, np.int32)
            for s in np.nonzero(self.active)[0]:
                n = int(self.hist_len[s])
                l = min(n, W)
                toks[s, :l] = self.history[s, n - l : n]
                lens[s] = max(l, 1)
                # Absolute position of toks[s, 0]: the drafter reads the
                # target's own pos_embed/RoPE at the true offsets.
                pos0[s] = n - l
            return np.asarray(
                self._draft(self.draft_params, toks, lens, pos0))
        for s in np.nonzero(self.active)[0]:
            drafts[s] = propose_ngram_drafts(
                self.history[s, : int(self.hist_len[s])], self.spec_k
            )
        return drafts

    def _propose_tree_drafts(self) -> np.ndarray:
        """(slots, spec_branches, spec_k) draft tree per slot. Branch 0 is
        EXACTLY :meth:`_propose_drafts`'s row (the linear drafter — learned
        or n-gram — which is what makes the tree's accepted-per-verify
        dominate the linear baseline pointwise); branches 1.. come from
        ``propose_ngram_tree`` over the slot's own history PLUS every other
        active slot's history — the batch-wide shared draft pool. Slots
        without enough distinct candidates repeat a filled branch, which
        the verify treats as a duplicate (harmless)."""
        B, D = self.spec_branches, self.spec_k
        tree = np.zeros((self.slots, B, D), np.int32)
        tree[:, 0, :] = self._propose_drafts()
        if B > 1:
            act = np.nonzero(self.active)[0]
            hists = {
                s: self.history[s, : int(self.hist_len[s])] for s in act
            }
            for s in act:
                alt = propose_ngram_tree(
                    hists[s], D, B,
                    extra_histories=[hists[o] for o in act if o != s],
                )
                tree[s, 1:, :] = alt[1:]
        return tree

    def _finish_round(self, rnd: _Round, nxt: _Round | None):
        """Wait for ``rnd`` and read it into the host registers. Slots the
        host touched while it was in flight keep the host's values and
        yield nothing from it; ``nxt``, queued from ``rnd``'s registers,
        learns here what it ran with."""
        active, lengths, tok, made, toks, valid = rnd.out[:6]
        # np.array (copy), not np.asarray: zero-copy views of jax buffers
        # are read-only, and start()/release() write these registers.
        with _trace.span("engine.wait", flight=False):
            # The round's first blocking read: the host waits here for the
            # device to finish the program.
            active = np.array(active)
        with _trace.span("engine.readback", flight=False):
            lengths = np.array(lengths)
            tok = np.array(tok)
            made = np.array(made)
            toks = np.asarray(toks)
            valid = np.asarray(valid)
            if not rnd.spec and len(rnd.out) > 6:  # routed experts
                rnd.moe = np.asarray(rnd.out[6])
                self.stats["moe_experts_touched"] += int(rnd.moe[0])
                self.stats["moe_tokens_routed"] += int(rnd.moe[2])
            if nxt is not None:
                nxt.was_active, nxt.lengths = active.copy(), lengths.copy()
            touched = self._touched
            if touched.any():
                for new, host in ((active, self.active),
                                  (lengths, self.lengths),
                                  (tok, self.cur_tok), (made, self.made)):
                    new[touched] = host[touched]
                valid = valid & ~touched
            self.active, self.lengths = active, lengths
            self.cur_tok, self.made = tok, made
            done = rnd.was_active & ~active & ~touched
            if self.spec_k:
                for s in np.nonzero(rnd.was_active & ~touched)[0]:
                    emitted = toks[valid[:, s], s]
                    n = int(self.hist_len[s])
                    self.history[s, n : n + emitted.size] = emitted
                    self.hist_len[s] = n + emitted.size
            if self.sentinel is not None:
                self.sentinel.poll(self.compile_count())
        return toks, valid, done

    # -- warmup / zero-recompile accounting -------------------------------

    def warmup(self) -> int:
        """Compile the full program set on throwaway requests; returns
        :meth:`compile_count`. Run this before taking traffic — after it,
        the count must never grow (the serving equivalent of
        ``__graft_entry__``'s collective-count asserts; asserted under
        churn in ``tests/test_serve_engine.py``). Covers: greedy prefill +
        PLAIN greedy step (forced even when speculation is on — the spec
        path falls back to it near max_len), BOTH speculative verify
        variants (greedy and rejection-sampling; the greedy pass also
        compiles the learned-draft program when one is loaded), the
        sampled prefill/step pair (the plain sampled step forced the
        same way when speculation is on), every prefill bucket width,
        and — when chunked prefill can trigger — one chunked prompt
        driven to completion (chunk calls reuse the bucket programs, so
        this compiles nothing new; it asserts that)."""
        with _trace.span("engine.warmup") as sp:
            passes: list[dict] = [{"temperature": 0.0, "_plain": True}]
            if self.spec_k:
                passes.append({"temperature": 0.0})
                # Sampled lanes take the spec path too (rejection-sampling
                # verify), so the plain sampled step needs its own forced
                # pass — it still serves the end-of-window fallback rounds.
                passes.append(
                    {"temperature": 1.0, "top_k": 2, "top_p": 0.9,
                     "_plain": True}
                )
            passes.append({"temperature": 1.0, "top_k": 2, "top_p": 0.9})
            for kwargs in passes:
                force = kwargs.pop("_plain", False)
                slot = self.acquire_slot()
                if slot is None:
                    raise RuntimeError("warmup needs a free slot")
                self._force_plain = force
                name = (("step" if force or not self.spec_k else "spec")
                        + (".sampled" if kwargs["temperature"] else ".greedy"))
                try:
                    with self._warm_program(name):
                        _, finished = self.start(
                            slot, [0], max_new_tokens=2, seed=0, **kwargs
                        )
                        if not finished:
                            while self.active[slot]:
                                self.step()
                            self.active[slot] = False
                finally:
                    self._force_plain = False
                    self.release(slot)
            # The passes above prefilled through the SMALLEST bucket (p=1);
            # compile the remaining widths too — a length-b throwaway prompt
            # forces bucket b exactly, and max_new=1 finishes at start() so
            # only the prefill programs are exercised. Adoption is disabled
            # for these passes: the greedy pass would otherwise insert its
            # [0]*width pages and the identical SAMPLED prompt would adopt
            # them and prefill through a smaller tail bucket, leaving the
            # full-width sampled prefill uncompiled (first sampled
            # prefill_len-wide prompt in traffic would then recompile).
            variants = (("greedy", {}),
                        ("sampled", {"temperature": 1.0, "top_k": 2}))
            prefix, self.prefix = self.prefix, None
            try:
                for width in self.prefill_buckets[1:]:
                    p_warm = min(width, self.max_len - 1)
                    for label, kwargs in variants:
                        slot = self.acquire_slot()
                        try:
                            with self._warm_program(f"prefill.{width}.{label}"):
                                self.start(slot, [0] * p_warm, max_new_tokens=1,
                                           seed=0, **kwargs)
                        finally:
                            self.release(slot)
            finally:
                self.prefix = prefix
            if 0 < self.prefill_chunk_tokens < self.max_len - 1:
                # One chunked prompt per sampling variant, driven through
                # step() to completion (budget 1 finishes at the final chunk).
                p_long = min(self.prefill_chunk_tokens + 1, self.max_len - 1)
                for label, kwargs in variants:
                    slot = self.acquire_slot()
                    try:
                        with self._warm_program(f"chunked.{label}"):
                            self.start(slot, [0] * p_long, max_new_tokens=1,
                                       seed=0, **kwargs)
                            while self.prefilling[slot]:
                                self.step()
                    finally:
                        self.release(slot)
            if self.prefix is not None:
                # Warmup's throwaway prompts must not linger as adoptable
                # prefixes (or skew the hit-rate counters).
                self.prefix.clear()
                self.prefix.tokens_matched = 0
                self.prefix.tokens_looked_up = 0
                self.stats["prefix_tokens_matched"] = 0
                self.stats["prefix_tokens_total"] = 0
            n = self.compile_count()
            if self.sentinel is not None:
                # Sync the poll base to the warmed cache size, then draw the
                # warm line: any compile the sentinel sees from here on counts
                # as recompile_events_total (the SLO-alerting condition).
                self.sentinel.poll(n)
                self.sentinel.mark_warm()
            sp.note(programs=n)
        return n

    @contextlib.contextmanager
    def _warm_program(self, program: str):
        """One ``engine.warmup_program`` span per program warm-up runs:
        its name and whether ``compile_count()`` grew (False: the program
        was there already, or came from the compile cache's memory)."""
        n0 = self.compile_count()
        with _trace.span("engine.warmup_program", program=program) as sp:
            yield
            sp.note(compiled=self.compile_count() > n0)

    def compile_count(self) -> int:
        """Total compiled programs across the engine's jitted callables —
        stable after :meth:`warmup` or something is shape-unstable."""
        fns = [self._prefill_greedy, self._prefill_sampled,
               self._step_greedy, self._step_sampled]
        if self._spec is not None:
            fns.append(self._spec)
        if self._spec_rs is not None:
            fns.append(self._spec_rs)
        if self._tree is not None:
            fns.append(self._tree)
        if self._tree_rs is not None:
            fns.append(self._tree_rs)
        if self._draft is not None:
            fns.append(self._draft)
        own = sum(
            f._cache_size() if hasattr(f, "_cache_size") else 0 for f in fns
        )
        return own + self.pool.compile_count()

    @property
    def mesh_device_count(self) -> int:
        """Devices the engine's programs span: 1 for the replicated base
        engine, ``mesh.size`` for the sharded one. Routers use this (via
        ``/healthz``) to tell one tp-wide replica from N independent ones."""
        return int(self.mesh.size) if self.mesh is not None else 1

    @property
    def param_device(self):
        """A device the placed params live on. ``/healthz`` reports its
        ``platform``/``device_kind`` so a client can refuse a server that
        landed on CPU."""
        return next(iter(jax.tree_util.tree_leaves(self.params)[0].devices()))

    @property
    def hbm_bytes_per_device(self) -> int:
        """KV pool bytes RESIDENT per device. The sharded engine splits
        the pool's kv-head axis ``tp`` ways; everything else about the
        pool (page tables, accounting) is host-side and free."""
        return int(self.pool.hbm_bytes) // max(1, self.tp)

    @property
    def weight_dtype(self) -> str:
        """Weight quantization mode serving this replica: ``'int8'`` /
        ``'int4'`` (``models/quant.py`` trees) or ``'native'`` for the
        stored high-precision weights. Surfaced through ``/healthz`` and
        the fleet registry so the router can tell variants apart."""
        return getattr(self.cfg, "weight_dtype", None) or "native"

    @property
    def draft_weight_dtype(self) -> str:
        """Quantization mode of the learned drafter (``''`` when the
        engine runs the host n-gram drafter — it has no weights). The
        issue contract quantizes the drafter HARDER than the target
        (int4 drafter over int8 target); this label lets dashboards
        verify that pairing per replica."""
        if self.draft_cfg is None:
            return ""
        return getattr(self.draft_cfg, "weight_dtype", None) or "native"

    @property
    def weight_bytes_per_device(self) -> int:
        """Target-model weight bytes RESIDENT per device (the drafter is
        accounted separately — it is small by construction). For sharded
        leaves the per-device share is the mean addressable-shard size
        (each mesh device holds exactly one shard: a split leaf counts
        ``nbytes/tp``, a replicated one full ``nbytes``)."""
        total = 0
        for leaf in jax.tree_util.tree_leaves(self.params):
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                total += sum(sh.data.nbytes for sh in shards) // len(shards)
            else:
                total += leaf.nbytes
        return int(total)

    # -- weight hot-swap (serve/deploy/) -----------------------------------
    #
    # ``self.params`` is a per-call traced argument to every jitted program
    # and is NEVER in a donate_argnums set (prefill donates the KV operand,
    # step donates the pool layers) — so replacing the reference between
    # rounds is donation-safe, and as long as the candidate matches the
    # live tree's structure/shapes/dtypes the jit signatures are unchanged:
    # zero recompiles by construction, which the RecompileSentinel then
    # asserts empirically.

    def check_swap_compatible(self, candidate) -> None:
        """Raise ``ValueError`` unless ``candidate`` has the live param
        tree's exact treedef, leaf shapes, and leaf dtypes — the validated
        precondition for a zero-recompile swap. Called before any device
        transfer so a wrong-architecture checkpoint is rejected for free."""
        cur, cur_def = jax.tree_util.tree_flatten(self.params)
        new, new_def = jax.tree_util.tree_flatten(candidate)
        if cur_def != new_def:
            raise ValueError(
                "adopt_weights: candidate tree structure differs from the "
                f"serving tree ({new_def} vs {cur_def})"
            )
        for i, (a, b) in enumerate(zip(cur, new)):
            if tuple(np.shape(a)) != tuple(np.shape(b)):
                raise ValueError(
                    f"adopt_weights: leaf {i} shape {np.shape(b)} != "
                    f"serving {np.shape(a)}"
                )
            da = jnp.asarray(a).dtype if not hasattr(a, "dtype") else a.dtype
            db = jnp.result_type(b)
            if np.dtype(da) != np.dtype(db):
                raise ValueError(
                    f"adopt_weights: leaf {i} dtype {db} != serving {da} "
                    "(a dtype change is a different jit signature — "
                    "recompile — so it must ship as a new replica, not a "
                    "hot swap)"
                )

    def _place_params(self, candidate):
        """Device placement for a swap candidate: plain device_put here;
        the sharded engine routes through its SERVE_TP_RULES shardings."""
        return jax.device_put(candidate)

    def stage_weights(self, candidate):
        """Validate + place a candidate param tree on the engine's devices
        WITHOUT touching the live reference — the double-buffer half of a
        hot swap. Safe to call from a watcher thread while the driver
        thread keeps decoding on the old buffers (the transfer allocates
        fresh buffers; nothing donates params). Returns the staged tree."""
        self.check_swap_compatible(candidate)
        return self._place_params(candidate)

    def adopt_weights(self, candidate, *, version=None, variant=None):
        """Flip the live param reference to ``candidate`` and return the
        previous tree (the rollback buffer). MUST be called between engine
        rounds on the driver thread — the scheduler's iteration boundary —
        so no jitted program is mid-flight on either buffer set. In-flight
        slots simply continue on the new weights next round; their KV
        cache carries over (same architecture by the precondition)."""
        candidate = self.stage_weights(candidate)
        prev, self.params = self.params, candidate
        if version is not None:
            self.weight_version = int(version)
        if variant is not None:
            self.serving_variant = str(variant)
        return prev

    # -- slot handoff (prefill tier -> decode tier) ------------------------
    #
    # Disaggregated serving moves a slot BETWEEN engines after prefill:
    # the prefill tier runs (possibly chunked) prefill to completion, then
    # exports the slot's KV pages plus the per-slot host registers; the
    # decode tier imports them and continues decoding. Token parity is by
    # construction: every sampling key is ``fold_in(PRNGKey(seed), made)``
    # and the registers travel exactly, so the continuation is the same
    # token stream local decode would have produced. Export gathers pages
    # eagerly and import scatters them eagerly + rebinds the (host numpy)
    # page table — no new jitted program on either side, so the
    # zero-recompile contract holds on both tiers.

    def _refuse_eva_handoff(self) -> None:
        """Slot handoff moves a plain page list: an EVA slot's composed
        row (kinds, windows done, forming pages) is not in that bundle."""
        if self.pool.state_leaves:
            raise SlotStateUnsupported(
                "slot export / import is not extended to per-slot state: "
                "the bundle would have to carry the slot's "
                + ("convolution state" if self._cca
                   else "recurrent and convolution state"))
        if self._eva:
            raise EvaUnsupported(
                "slot export/import is not extended to EVA's composed "
                "page table")
        if self._mla:
            raise SlotStateUnsupported(
                "slot export/import is not extended to latent attention: "
                "the bundle carries K and V rows")

    def export_slot(self, slot: int, *, history=None) -> dict:
        """Capture ``slot``'s decode state as a host-serializable bundle.

        The slot must be post-prefill and still active (a request that
        finished at its first token has nothing to hand off). ``history``
        (prompt + emitted tokens) feeds the importing engine's drafter;
        when the exporter tracks history itself (``spec_k > 0``) its own
        register wins. The slot stays live here — the caller releases it
        only once the peer acknowledged the import (fallback to local
        decode otherwise, so no request is ever lost)."""
        self._refuse_eva_handoff()
        if self.prefilling[slot]:
            raise RuntimeError(f"slot {slot} is mid-chunked-prefill")
        if not self.active[slot]:
            raise RuntimeError(f"slot {slot} is not active")
        if self.spec_k:
            history = self.history[slot, : int(self.hist_len[slot])]
        hist = (np.asarray(history, np.int32).ravel().tolist()
                if history is not None else [])
        return {
            "length": int(self.lengths[slot]),
            "cur_tok": int(self.cur_tok[slot]),
            "made": int(self.made[slot]),
            "budget": int(self.budget[slot]),
            "eos": int(self.eos[slot]),
            "temperature": float(self.temp[slot]),
            "top_k": int(self.top_k[slot]),
            "top_p": float(self.top_p[slot]),
            "seed": int(self.seed[slot]),
            "history": hist,
            "page_size": self.page_size,
            "kv_dtype": self.kv_dtype,
            "pages": self.pool.export_pages(slot),
        }

    def export_slot_meta(self, slot: int, *, history=None) -> dict:
        """The v2 (streaming) flavor of :meth:`export_slot`: identical
        registers, but the page leaves come from
        ``pool.snapshot_pages`` — device arrays whose gathers were only
        DISPATCHED. The driver thread pays microseconds of op dispatch
        instead of the whole device->host copy; the outbox worker pulls
        rows to host chunk by chunk while streaming. Same preconditions
        and the same exporter-keeps-the-slot contract as
        :meth:`export_slot`."""
        self._refuse_eva_handoff()
        if self.prefilling[slot]:
            raise RuntimeError(f"slot {slot} is mid-chunked-prefill")
        if not self.active[slot]:
            raise RuntimeError(f"slot {slot} is not active")
        if self.spec_k:
            history = self.history[slot, : int(self.hist_len[slot])]
        hist = (np.asarray(history, np.int32).ravel().tolist()
                if history is not None else [])
        return {
            "length": int(self.lengths[slot]),
            "cur_tok": int(self.cur_tok[slot]),
            "made": int(self.made[slot]),
            "budget": int(self.budget[slot]),
            "eos": int(self.eos[slot]),
            "temperature": float(self.temp[slot]),
            "top_k": int(self.top_k[slot]),
            "top_p": float(self.top_p[slot]),
            "seed": int(self.seed[slot]),
            "history": hist,
            "page_size": self.page_size,
            "kv_dtype": self.kv_dtype,
            "pages": self.pool.snapshot_pages(slot),
        }

    def import_slot(self, slot: int, bundle: dict) -> None:
        """Adopt an exported slot bundle into a freshly acquired ``slot``.

        Raises :class:`InsufficientPages` (slot registers untouched — the
        caller releases the slot and retries or tells the exporter to
        decode locally) when the pool cannot back the payload. On success
        the slot is active and the next :meth:`step` continues the
        request exactly where the exporter stopped."""
        self._refuse_eva_handoff()
        self.validate_handoff_header(bundle)
        self.pool.import_pages(slot, bundle["pages"])
        self._adopt_handoff_registers(slot, bundle)

    def adopt_imported_slot(self, slot: int, bundle: dict,
                            page_ids) -> None:
        """Commit a STAGED (chunk-streamed) import: ``page_ids`` were
        already allocated and scattered incrementally; bind them to
        ``slot`` and adopt the bundle's registers. The registers-only
        counterpart of :meth:`import_slot` — the all-or-nothing contract
        holds because nothing is bound or activated until this call, and
        the abort path frees the staged pages without touching a slot."""
        self._refuse_eva_handoff()
        self.validate_handoff_header(bundle)
        self.pool.bind(slot, list(page_ids))
        self._adopt_handoff_registers(slot, bundle)

    def validate_handoff_header(self, bundle: dict) -> None:
        """Typed pre-import validation (page geometry, KV format, length
        headroom) — shared by the whole-bundle and staged import paths, and
        cheap enough for a receiver to run BEFORE reading page bytes."""
        if bundle["page_size"] != self.page_size:
            raise ValueError(
                f"handoff page_size {bundle['page_size']} != engine "
                f"page_size {self.page_size}"
            )
        # KV format must match EXACTLY: the pool's import scatters raw
        # rows into its own leaves by name, so an int8 bundle landing in a
        # bf16 pool (or vice versa) would silently cast rows without their
        # scales — garbage KV, not an error. A typed ValueError here takes
        # the scheduler's existing "invalid" fallback instead (exporter
        # decodes locally; no request lost, no silent dequant). Absent key
        # = pre-PR-14 exporter: permissive, formats were implicitly equal.
        kd = str(bundle.get("kv_dtype", "") or "")
        if kd and kd != self.kv_dtype:
            raise ValueError(
                f"handoff kv_dtype {kd!r} != engine kv_dtype "
                f"{self.kv_dtype!r}"
            )
        length = int(bundle["length"])
        headroom = int(bundle["budget"]) - int(bundle["made"])
        if length + headroom > self.max_len:
            raise ValueError(
                f"handoff length {length} + {headroom} remaining > engine "
                f"max_len {self.max_len}"
            )

    def _adopt_handoff_registers(self, slot: int, bundle: dict) -> None:
        self._touched[slot] = True
        self.active[slot] = True
        self.prefilling[slot] = False
        self.lengths[slot] = int(bundle["length"])
        self.cur_tok[slot] = int(bundle["cur_tok"])
        self.temp[slot] = float(bundle["temperature"])
        self.top_k[slot] = int(bundle["top_k"])
        self.top_p[slot] = float(bundle["top_p"])
        self.seed[slot] = np.uint32(int(bundle["seed"]) & 0xFFFFFFFF)
        self.made[slot] = int(bundle["made"])
        self.budget[slot] = int(bundle["budget"])
        self.eos[slot] = int(bundle["eos"])
        if self.spec_k:
            hist = np.asarray(bundle.get("history", ()), np.int32).ravel()
            hist = hist[: self.max_len]
            self.history[slot, : hist.size] = hist
            self.hist_len[slot] = hist.size
        if self.sentinel is not None:
            self.sentinel.poll(self.compile_count())


class ShardedSlotEngine(SlotEngine):
    """The SlotEngine on a TP-partitioned model — same slot API, same
    host-side registers and page tables, same fixed compiled-program set,
    but every program is jitted under a ``('data', 'model')`` mesh
    (``data`` axis size 1 — serving parallelism is slots, not batch):

    * **Weights** are placed by the declarative rule table
      (``parallel/rules.py::SERVE_TP_RULES`` unless ``rules=`` overrides):
      fused qkv / mlp_in column-parallel, proj / mlp_out row-parallel,
      embeddings + norms + lm_head replicated. ``in_shardings`` pin the
      same placement at every program boundary so donated buffers round-trip
      without resharding.
    * **KV pool** leaves shard along the kv-head axis
      (``P(None, 'model')`` — pages and in-page positions stay whole), the
      axis GQA-under-TP already constrains to ``num_kv_heads % tp == 0``.
    * **Everything host-side stays host-side and replicated**: page
      tables, slot registers, token buffers enter as numpy traced operands
      exactly as before, so rebinding pages never retraces and the
      zero-recompile-after-warmup contract (RecompileSentinel) is
      unchanged.

    GSPMD jit semantics make this a PLACEMENT change, not a numerics
    rewrite: XLA partitions the matmuls along the annotated dims and
    inserts the collectives, and the emitted TOKENS are identical to the
    single-device engine (asserted by the sharded_serve parity tests and
    in ``bench_serving_sharded``).
    """

    def __init__(
        self,
        cfg,
        params,
        *,
        tp: int,
        devices=None,
        rules=None,
        **kw,
    ):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from distributed_tensorflow_tpu.config import validate_tp_mesh
        from distributed_tensorflow_tpu.parallel.mesh import make_mesh
        from distributed_tensorflow_tpu.parallel.rules import (
            SERVE_TP_RULES,
            shardings_from_rules,
        )

        tp = int(tp)
        if tp < 2:
            raise ValueError(
                f"ShardedSlotEngine is the tp >= 2 path, got tp={tp}; "
                "use SlotEngine for a single-device replica"
            )
        validate_tp_mesh(cfg, tp)
        if getattr(cfg, "weight_dtype", None):
            from distributed_tensorflow_tpu.models.quant import (
                validate_weight_quant,
            )

            # TP adds a constraint config-time validation can't know: the
            # row-parallel int4 shards must hold whole scale groups.
            validate_weight_quant(
                cfg.weight_dtype, cfg.quant_group_size, cfg.d_model,
                cfg.d_ff, tp=tp,
            )
        devices = list(devices) if devices is not None else list(jax.devices())
        if len(devices) < tp:
            raise ValueError(
                f"tp={tp} needs {tp} devices but only {len(devices)} are "
                "visible (CPU smoke: set XLA_FLAGS="
                "--xla_force_host_platform_device_count)"
            )
        # Set BEFORE delegating: the base __init__ calls the _build_pool /
        # _jit_program hooks below, which read the mesh state.
        self.tp = tp
        self.mesh = make_mesh(
            num_devices=tp, model_parallel=tp, devices=devices[:tp]
        )
        self._rep = NamedSharding(self.mesh, P())
        # One spec covers every pool leaf: axis 1 is kv heads on both the
        # (pages, kv, ps, dh) k/v rows and the (pages, kv, ps) int8 scales;
        # unnamed trailing dims are replicated.
        self._kv_shard = NamedSharding(self.mesh, P(None, "model"))
        self._rules = tuple(rules) if rules is not None else SERVE_TP_RULES
        self._param_sh = shardings_from_rules(self._rules, params, self.mesh)
        params = jax.device_put(params, self._param_sh)
        super().__init__(cfg, params, **kw)

    # -- hooks -------------------------------------------------------------

    def _build_pool(self, cfg, max_len, kv_pages):
        return PagedKVPool(
            cfg, self.slots, max_len, self.page_size, kv_pages,
            kv_sharding=self._kv_shard,
        )

    def _decode_path(self) -> str:
        # GSPMD does not partition a Pallas call: reading the pages in
        # place here would need shard_map over the kv heads.
        return "gather"

    def _prefill_path(self) -> str:
        # The same reason, for the chunk's kernel.
        return "dense"

    def _place_params(self, candidate):
        # Swap candidates stage through the SAME rule-table shardings as
        # the boot-time params, so the jitted programs' in_shardings keep
        # matching and the flip stays resharding- and recompile-free.
        return jax.device_put(candidate, self._param_sh)

    def _put(self, host: tuple) -> tuple:
        # As the step programs give them back (out_shardings): the same
        # signature whether a round is queued from the host or run ahead.
        return jax.device_put(host, self._rep)

    def _jit_program(self, fn, kind, donate):
        """Jit under the mesh with explicit in/out shardings per program
        kind. Arg layouts are the base engine's (position 0 = pool layers,
        position 1 = params, everything after is a replicated host
        register); the pool position takes ONE sharding as a pytree
        prefix for all its leaves."""
        rep, kvs, psh = self._rep, self._kv_shard, self._param_sh
        if kind == "draft":
            # The drafter is a small replicated model over host windows —
            # nothing sharded flows through it.
            return jax.jit(fn, donate_argnums=donate)
        if kind == "prefill":
            # (pool, params, tokens, length, prefix_len, row, temp,
            #  top_k, top_p, seed) -> (pool, first)
            ins = (kvs, psh) + (rep,) * 8
            outs = (kvs, rep)
        elif kind == "step":
            # (pool, params, ptabs, active, lengths, tok, temp, top_k,
            #  top_p, seed, made, budget, eos)
            #   -> (pool, active, lengths, tok, made, toks, valid)
            ins = (kvs, psh) + (rep,) * 11
            outs = (kvs,) + (rep,) * 6
        elif kind in ("spec", "tree"):
            # (pool, params, ptabs, active, lengths, tok, drafts, temp,
            #  top_k, top_p, seed, made, budget, eos) -> (pool, active,
            #  lengths, tok, made, emitted.T, valid.T, accepted). The tree
            #  verify has the same layout — drafts is (slots, B, D)
            #  instead of (slots, k), still one replicated host operand.
            ins = (kvs, psh) + (rep,) * 12
            outs = (kvs,) + (rep,) * 7
        else:  # pragma: no cover - new kinds must be wired explicitly
            raise ValueError(f"unknown program kind {kind!r}")
        return jax.jit(
            fn, donate_argnums=donate, in_shardings=ins, out_shardings=outs
        )
