"""KV storage for the serving engine: the block-paged pool.

:class:`PagedKVPool` is the vLLM PagedAttention layout: ONE physical pool
of fixed-size pages per layer, shaped ``(num_pages, kv_heads, page_size,
head_dim)``, plus a host-side per-slot page table ``(slots,
pages_per_slot)`` of physical page ids, and the slot free list
(``alloc``/``free``/``num_free``/``occupancy``). A slot's logical ``(kv,
max_len, dh)`` cache is the gather of its table row; capacity is
PAGES-free, not slots-free, so short requests reserve no worst-case HBM
and the same pool admits more concurrent requests. Physical page 0 is a
reserved TRASH page: unbound table entries point at it, masked/inactive
lanes scatter into it, and nothing ever reads it — which is what lets
every jitted program keep fixed shapes (full-width table rows, full-width
scatters) with zero recompiles.

Pages are REFCOUNTED so immutable full-prompt pages can be shared between
slots (and held by the :class:`PrefixCache`): a slot's allocation holds one
reference, prefix adoption adds one per adopting slot, and the cache holds
one of its own. A page returns to the free list only at refcount zero.
Safety of sharing rests on the overwrite invariant of slot reuse (see
``engine.py``): decode writes start at the filled
length ``p`` (strictly above every full prompt page), so a shared page is
written only with byte-identical content (the prefill program's
whole-row scatter-back, which round-trips the gathered values).

The :class:`PrefixCache` keys pages by the EXACT token prefix they complete
(the entry of the prefix before a page, by an id never handed out twice, and
the raw bytes of the page's own tokens; not a hash digest), so a lookup can
never adopt a colliding request's KV; entries are LRU-evicted when the pool
runs out of pages.

**Two kinds of page (an EVA config, ``cfg.eva_window`` set).** The same
leaves ``(num_pages, heads, page_size, head_dim)`` and the same refcounts
hold two kinds of page, told apart by where a slot's table points at them:

* a WINDOW page: ``page_size`` K/V rows of consecutive positions, as above;
* a SUMMARY page: ``page_size`` chunk summaries (k~, v~), one for every
  ``eva_chunk`` positions (a chunk is one page), so it stands for
  ``page_size * eva_chunk`` positions.

A slot's table row is COMPOSED: ``[summary pages of its finished windows |
pages of its current window | ... trash ... | forming pages]``. The first
two runs are what its next token attends, in that order, as one run of
logical rows (``attend = summaries + window rows so far + 1``): the paged
decode kernel and the prefill's gathered logical cache see nothing else.
The row's last ``sum_pages`` entries are the FORMING summary pages of the
current window: written as chunks fill (by the decode program, or by a
prefill segment for every whole chunk of the window so far), never attended.
``pages_per_slot`` counts the attended entries: ``(windows - 1) * sum_pages +
window_pages`` (248 at 32768 positions, windows of 2048, pages of 16).

**State that is not rows (a CCA config, ``cfg.cca_time0`` set).** CCA's
convolutions and value shift need, of the tokens before, the last
``cfg.cca_hist`` positions' PRE-convolution latents and shifted value half:
not rows of the cache, and not recomputable from it. The pool owns them as
one more leaf of every layer, ``cca`` ``(slots, cca_hist, cca_state_width)``
beside ``k`` / ``v``: indexed by SLOT, not by page, so it is kept with the
slot's table row and released with it, and it rides the same donation
through every engine program as the pages do (the decode round's registers:
round n+1 is queued from round n's leaf with no host read). Zeroing is the
prefill program's: a segment at position 0 reads zeros whatever the slot's
last owner left (the equations' "positions before 0 are zero"), every
segment writes the state behind its last REAL token, every decode step
behind its token, a masked lane leaves it alone. Nothing that walks pages
(export / import, the prefix cache) is extended to it; the engine refuses
those for such a config.

**Layers of one kind each (a ``layer_pattern`` config).** A layer holds the
leaves of its kind: ``k`` / ``v`` pages for an attention layer (``*``) alone,
so one page table a slot serves those layers and a page costs their rows
only; for a Mamba-2 layer (``M``) two per-slot leaves in the place CCA's has,
``ssm`` ``(slots, ssm_heads, ssm_head_dim, ssm_state)`` in float32 (the
recurrent state: it is summed into at every token, and the family asks
servers for a float32 one) and ``conv`` ``(slots, ssm_conv - 1,
ssm_conv_width)`` in the cache's dtype (the convolution's last inputs);
nothing for an expert layer (``E``). The state leaves are zeroed, kept and
released with the slot exactly as ``cca`` is, and refused by the same
walkers; :attr:`PagedKVPool.state_bytes` counts them.

**When a page is released.** At a window's end (:meth:`PagedKVPool.
roll_window`, on the host between two rounds): the slot drops its reference
on each of the window's pages, so a page returns to the free list unless the
prefix cache or another slot still holds it; the forming pages become the
window's summary pages in the table; fresh pages are bound for the next
window, as many as the request will fill, with fresh forming pages if it
will finish that window too. So pages ARE allocated mid-request, which the
plain layout never does; :meth:`PagedKVPool.reserve` keeps that safe: at
admission a slot sets aside the most it will hold at once
(:meth:`PagedKVPool.pages_needed`), admission fails with
:class:`InsufficientPages` when the slots' reservations together would pass
the pool, and until they do every page a roll asks for is free or held by
the prefix cache alone, which gives it up (``evict``).

**What a prefix adopts.** A prompt's finished windows are indexed by their
summary pages (every page of a window, each keyed by the prefix its
summaries end at, under a key of its own kind), and the window the prompt
ends in by its full K/V pages, chained from the summaries of the windows
before it. A request adopts the summary pages of every leading window cached
whole, then the chain of full K/V pages of the window after them
(:meth:`PrefixCache.match_eva`); the summaries of the chunks it adopted as
K/V pages are formed again from those rows by its first prefill segment.
"""

from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_tpu.models.decoding import init_cache

__all__ = [
    "PagedKVPool",
    "PrefixCache",
    "InsufficientPages",
    "TRASH_PAGE",
]

# Physical page 0: never allocated, never freed, absorbs the fixed-shape
# scatters of unbound table entries and masked lanes. Never read.
TRASH_PAGE = 0


# Fused page scatter for the chunk-streamed handoff import: one jitted
# dispatch updates every leaf of every layer, so the driver-thread block
# per staged chunk is bounded by a single program launch instead of
# layers x leaves eager dispatches (that per-leaf loop is exactly what
# makes the v1 monolithic import a long stall on deep models). Donation
# recycles the pool buffers in place; the caller rebinds ``pool.layers``
# to the result immediately, which is what makes donation safe.
def _page_scatter(layers, idx, rows):
    return jax.tree_util.tree_map(
        lambda buf, r: buf.at[idx].set(r), layers, rows)


_fused_page_scatter = jax.jit(_page_scatter, donate_argnums=(0,))


class InsufficientPages(RuntimeError):
    """Admission-time: the pool cannot back this request right now. The
    scheduler requeues the request at the head of its lane — pages free as
    in-flight requests complete, so progress is guaranteed (every active
    request holds ALL its pages up front; nothing allocates mid-decode)."""


class PagedKVPool:
    """Block-granular physical KV pool + per-slot page tables.

    Pure host bookkeeping plus the big device buffers — every jitted
    mutation (prefill scatter, decode page write-back) lives in the
    engine's programs, which take ``layers`` (donated) and a table row /
    the full table as traced operands. ``page_tables`` is host numpy so
    the scheduler's view of capacity never needs a device sync.
    """

    def __init__(self, cfg, slots: int, max_len: int, page_size: int,
                 num_pages: int = 0, kv_sharding=None):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if max_len % page_size:
            raise ValueError(
                f"max_len {max_len} must be a multiple of page_size "
                f"{page_size} (fixed-shape table rows need a whole number "
                f"of pages per slot)"
            )
        self.cfg = cfg
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.page_size = int(page_size)
        self.pages_per_slot = max_len // page_size
        # EVA (cfg.eva_window set): the composed layout of the module
        # docstring. ``window_pages`` pages hold one window's K/V rows and
        # ``sum_pages`` pages its summaries; a table row is the summary
        # pages of every window but the last, then one window's pages
        # (``pages_per_slot``: what is ever attended), then the
        # ``sum_pages`` forming pages, which are written and not attended.
        self.eva = bool(getattr(cfg, "eva", False))
        self.window_pages = self.sum_pages = 0
        if self.eva:
            w, c = int(cfg.eva_window), int(cfg.eva_chunk)
            if c != page_size or (w // c) % page_size or max_len % w:
                raise ValueError(
                    f"an EVA pool needs page_size {page_size} == eva_chunk "
                    f"{c}, a window's {w // c} summaries a whole number of "
                    f"pages, and max_len {max_len} a whole number of "
                    f"windows of {w}")
            self.window = w
            self.window_pages = w // page_size
            self.sum_pages = w // c // page_size
            self.pages_per_slot = (
                (max_len // w - 1) * self.sum_pages + self.window_pages)
        if num_pages == 0:
            # Default: worst case for every slot + the trash page — paging
            # with no oversubscription. Sizing BELOW this is the point:
            # short requests only claim what they use, so the same HBM
            # admits more concurrent requests.
            num_pages = self.slots * (self.pages_per_slot + self.sum_pages) + 1
        if num_pages < self.pages_per_slot + 1:
            raise ValueError(
                f"num_pages {num_pages} cannot back even one worst-case "
                f"request ({self.pages_per_slot} pages) + the trash page"
            )
        self.num_pages = int(num_pages)
        # One allocation for the pool's lifetime: init_cache with
        # batch=num_pages, len=page_size IS the paged layout — every cache
        # variant the model family supports (GQA kv heads, int8 rows with
        # f32 scales) pages identically. ``kv_sharding`` (a NamedSharding,
        # from ShardedSlotEngine) allocates the buffers already split on
        # the kv-head axis — every leaf has kv heads at axis 1, so one
        # sharding covers them all; page tables below stay host numpy
        # either way.
        self.kv_sharding = kv_sharding
        self.layers = init_cache(
            cfg, self.num_pages, page_size, sharding=kv_sharding
        )["layers"]
        # State that is not rows (the module docstring): CCA's per-slot
        # convolution latents, one leaf a layer beside its pages; a Mamba-2
        # layer's recurrent and convolution state, two leaves and no pages.
        self.cca = bool(getattr(cfg, "cca", False))
        pattern = getattr(cfg, "layer_pattern", None) or ""
        self.state_leaves = (("cca",) if self.cca
                             else ("ssm", "conv") if "M" in pattern else ())
        if self.state_leaves and kv_sharding is not None:
            raise ValueError("a pool with per-slot state is not sharded")
        for i, layer in enumerate(self.layers):
            if self.cca:
                layer["cca"] = jnp.zeros(
                    (self.slots, cfg.cca_hist, cfg.cca_state_width),
                    cfg.compute_dtype)
            elif pattern[i:i + 1] == "M":
                layer["ssm"] = jnp.zeros(
                    (self.slots, cfg.ssm_heads, cfg.ssm_head_dim,
                     cfg.ssm_state), jnp.float32)
                layer["conv"] = jnp.zeros(
                    (self.slots, cfg.ssm_conv - 1, cfg.ssm_conv_width),
                    cfg.compute_dtype)
        # Page 0 is TRASH (reserved, refcount pinned). Free pages are a mask
        # over the ids, so that :meth:`alloc_pages` sees them as runs of
        # neighbours; free slots a LIFO list with a companion set that keeps
        # the double-free check O(1) under churn.
        self._page_free = np.ones(self.num_pages, bool)
        self._page_free[TRASH_PAGE] = False
        self._pages_free = self.num_pages - 1
        self.refcount = np.zeros(self.num_pages, np.int64)
        self.refcount[TRASH_PAGE] = 1  # pinned — never allocatable
        self.page_tables = np.full(
            (self.slots, self.pages_per_slot + self.sum_pages), TRASH_PAGE,
            np.int32,
        )
        # EVA: finished windows in each slot's table, and the most pages
        # each slot may hold at once (:meth:`reserve`).
        self.windows_done = np.zeros(self.slots, np.int64)
        self.reserved = np.zeros(self.slots, np.int64)
        self._free_slots: list[int] = list(range(slots - 1, -1, -1))
        self._free_slot_set: set[int] = set(self._free_slots)

    # -- capacity views ---------------------------------------------------

    @property
    def num_free(self) -> int:
        """Free SLOT count (engine lane capacity; pages gate separately)."""
        return len(self._free_slots)

    @property
    def pages_free(self) -> int:
        return self._pages_free

    @property
    def pages_allocatable(self) -> int:
        return self.num_pages - 1  # minus trash

    @property
    def occupancy(self) -> float:
        """PAGE occupancy — under paging, capacity is pages-free."""
        return 1.0 - self.pages_free / self.pages_allocatable

    @property
    def hbm_bytes(self) -> int:
        return sum(
            buf.size * buf.dtype.itemsize
            for layer in self.layers
            for buf in layer.values()
        )

    @property
    def state_bytes(self) -> int:
        """Bytes of the per-slot state leaves (``state_leaves``), which
        ``hbm_bytes`` includes: what is held by slot and not by page."""
        return sum(
            layer[name].size * layer[name].dtype.itemsize
            for layer in self.layers for name in self.state_leaves
            if name in layer
        )

    @property
    def hbm_bytes_per_slot(self) -> float:
        return self.hbm_bytes / self.slots

    @property
    def bytes_per_token(self) -> float:
        """KV bytes one token position costs across all layers in the
        live page format (int8 rows include their f32 scale planes) —
        ``hbm_bytes`` spread over every page's positions. This is the
        byte-diet ratio's numerator/denominator: at int8 the same HBM
        backs proportionally more pages, which is the page-capacity gain
        ``bench_serving`` demonstrates in-run."""
        return (self.hbm_bytes - self.state_bytes) / (
            self.num_pages * self.page_size)

    def pages_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        """Pages a request of ``prompt_len + max_new_tokens`` positions
        holds. Plain layout: one page for every ``page_size`` positions,
        all bound at admission. EVA layout: the MOST it holds at once, which
        is what admission reserves (:meth:`reserve`): with ``n`` windows
        behind the one its last position lies in, ``n`` windows of summary
        pages and, where n > 0, one whole window of pages (in the window
        before, the forming summary pages stand in for the last window's
        summaries); pages are bound window by window (:meth:`roll_window`)."""
        total = prompt_len + max_new_tokens
        if self.eva:
            n = (total - 1) // self.window
            if n:
                return n * self.sum_pages + self.window_pages
        return -(-total // self.page_size)  # ceil

    def pages_bound(self, slot: int) -> int:
        """Pages currently bound in ``slot``'s table row (TRASH excluded)
        — the accounting view multi-iteration (chunked) prefill is audited
        against: admission must bind exactly ``pages_needed(p, n)`` pages
        up front (adopted + owned), and ``free()`` must return every
        non-shared one."""
        if not 0 <= slot < self.slots:
            raise ValueError(f"slot {slot} outside [0, {self.slots})")
        return int(np.count_nonzero(self.page_tables[slot] != TRASH_PAGE))

    # -- slot bookkeeping --------------------------------------------------

    def alloc(self) -> int | None:
        """Claim a slot index, or None when every slot is taken."""
        if not self._free_slots:
            return None
        slot = self._free_slots.pop()
        self._free_slot_set.discard(slot)
        return slot

    def free(self, slot: int) -> None:
        """Release a slot AND its page references. The table row resets to
        TRASH so a later (masked) lane write can never land in a page that
        has been handed to another slot — the stale-page-table hazard."""
        if not 0 <= slot < self.slots:
            raise ValueError(f"slot {slot} outside [0, {self.slots})")
        if slot in self._free_slot_set:
            raise ValueError(f"double free of slot {slot}")
        for pid in self.page_tables[slot]:
            if pid != TRASH_PAGE:
                self.decref(int(pid))
        self.page_tables[slot, :] = TRASH_PAGE
        self.windows_done[slot] = 0
        self.reserved[slot] = 0
        self._free_slots.append(slot)
        self._free_slot_set.add(slot)

    # -- page bookkeeping --------------------------------------------------

    def alloc_pages(self, n: int) -> list[int] | None:
        """Claim ``n`` physical pages (refcount 1 each), or None if too few
        are free — the caller may evict prefix-cache entries and retry.
        All-or-nothing: no partial claims to unwind. The pages come as
        neighbours, in ascending order: the smallest run of free ids that
        holds the request whole, else the largest runs first. A table row
        whose ids ascend by one is what the paged decode kernel copies with
        one descriptor a step (``ops.attention.paged_decode_chain``); popped
        from a list in the order rows were released they came apart a
        little more with every admission (3.4-3.6 live pages a copy over
        cell 6's churn where this reads 6.5-6.8: PERF.md, PR 38)."""
        if n > self._pages_free:
            return None
        if n == 0:
            return []
        free = np.flatnonzero(self._page_free)
        starts = np.flatnonzero(np.diff(free, prepend=-1) != 1)
        sizes = np.diff(starts, append=free.size)
        fits = np.flatnonzero(sizes >= n)
        if fits.size:
            first = starts[fits[np.argmin(sizes[fits])]]
            pages = free[first : first + n]
        else:
            order = np.argsort(-sizes, kind="stable")
            need = n - np.cumsum(sizes[order]) + sizes[order]  # before each
            pages = np.sort(np.concatenate([
                free[starts[i] : starts[i] + min(sizes[i], left)]
                for i, left in zip(order, need) if left > 0]))
        self._page_free[pages] = False
        self._pages_free -= n
        self.refcount[pages] = 1
        return pages.tolist()

    def incref(self, pid: int) -> None:
        if pid == TRASH_PAGE or not 0 < pid < self.num_pages:
            raise ValueError(f"incref of invalid page {pid}")
        if self._page_free[pid]:
            raise ValueError(f"incref of free page {pid}")
        self.refcount[pid] += 1

    def decref(self, pid: int) -> None:
        if pid == TRASH_PAGE or not 0 < pid < self.num_pages:
            raise ValueError(f"decref of invalid page {pid}")
        if self._page_free[pid]:
            raise ValueError(f"double free of page {pid}")
        self.refcount[pid] -= 1
        if self.refcount[pid] == 0:
            self._page_free[pid] = True
            self._pages_free += 1

    def bind(self, slot: int, page_ids: list[int]) -> None:
        """Point ``slot``'s table at ``page_ids`` (prefix-adopted pages
        first, then the slot's own); unbound tail entries stay TRASH."""
        if len(page_ids) > self.pages_per_slot:
            raise ValueError(
                f"{len(page_ids)} pages > pages_per_slot {self.pages_per_slot}"
            )
        self.page_tables[slot, :] = TRASH_PAGE
        self.page_tables[slot, : len(page_ids)] = np.asarray(
            page_ids, np.int32
        )

    # -- EVA: the composed table ---------------------------------------------

    def reserve(self, slot: int, n: int) -> bool:
        """Set aside ``n`` pages as the most ``slot`` will hold at once.
        False when the slots' reservations together would pass the pool:
        then a later :meth:`roll_window` could find no page. While they do
        not, every page a roll asks for is free or held by the prefix cache
        alone, and the cache gives it up."""
        if self.reserved.sum() - self.reserved[slot] + n > self.pages_allocatable:
            return False
        self.reserved[slot] = n
        return True

    def bind_eva(self, slot: int, summary_pages, window_pages,
                 forming_pages) -> None:
        """Point ``slot``'s table at its composed row: the summary pages of
        its finished windows (``sum_pages`` each), its current window's
        pages, and, in the row's last ``sum_pages`` entries, the forming
        summary pages (none: the window will not be finished)."""
        n_sum = len(summary_pages)
        if n_sum % self.sum_pages or len(window_pages) > self.window_pages:
            raise ValueError(
                f"{n_sum} summary pages / {len(window_pages)} window pages "
                f"do not compose a row")
        row = self.page_tables[slot]
        row[:] = TRASH_PAGE
        row[:n_sum] = np.asarray(summary_pages, np.int32)
        row[n_sum:n_sum + len(window_pages)] = np.asarray(
            window_pages, np.int32)
        row[self.pages_per_slot:self.pages_per_slot + len(forming_pages)] = (
            np.asarray(forming_pages, np.int32))
        self.windows_done[slot] = n_sum // self.sum_pages

    def window_row(self, slot: int) -> np.ndarray:
        """The entries of ``slot``'s row that hold its current window."""
        lo = int(self.windows_done[slot]) * self.sum_pages
        return self.page_tables[slot, lo:lo + self.window_pages]

    def forming_row(self, slot: int) -> np.ndarray:
        return self.page_tables[slot, self.pages_per_slot:]

    def roll_window(self, slot: int, n_window: int, forming: bool,
                    evict=None) -> int:
        """``slot``'s current window is finished: its pages are released
        (one reference each: a page the prefix cache or another slot still
        holds stays resident), its forming summary pages join the summaries
        in the table, and ``n_window`` fresh pages are bound for the next
        window, with fresh forming pages if ``forming``. ``evict(n)`` is
        asked to free pages where the free list is short
        (``PrefixCache.evict_for``). Returns the pages released."""
        row = self.page_tables[slot]
        done = int(self.windows_done[slot])
        lo = done * self.sum_pages
        old = [int(p) for p in self.window_row(slot) if p != TRASH_PAGE]
        formed = self.forming_row(slot).copy()
        if (formed == TRASH_PAGE).any():
            raise RuntimeError(
                f"slot {slot} finished a window without forming pages")
        for pid in old:
            self.decref(pid)
        want = n_window + (self.sum_pages if forming else 0)
        fresh = self.alloc_pages(want)
        if fresh is None and evict is not None:
            evict(want)
            fresh = self.alloc_pages(want)
        if fresh is None:
            raise InsufficientPages(
                f"window roll of slot {slot} needs {want} pages, "
                f"{self.pages_free} free: reservations were passed over")
        row[lo:] = TRASH_PAGE
        row[lo:lo + self.sum_pages] = formed
        lo += self.sum_pages
        row[lo:lo + n_window] = np.asarray(fresh[:n_window], np.int32)
        row[self.pages_per_slot:self.pages_per_slot + want - n_window] = (
            np.asarray(fresh[n_window:], np.int32))
        self.windows_done[slot] = done + 1
        return len(old)

    def compile_count(self) -> int:
        return 0  # all jitted programs live in the engine

    # -- page handoff (prefill -> decode tier) -----------------------------

    def export_pages(self, slot: int) -> dict:
        """Gather ``slot``'s bound pages to host numpy as a handoff payload.

        The payload is layout-generic: every cache leaf (k/v rows, and the
        f32 scale planes of an int8 cache) is gathered at the same physical
        page indices, so int8 pages travel as rows+scales with no special
        casing. Pure eager reads — no new jitted program, the slot's pages
        stay bound and refcounted on this pool (the exporter frees them via
        the normal ``free(slot)`` path once the handoff is acknowledged).
        """
        if not 0 <= slot < self.slots:
            raise ValueError(f"slot {slot} outside [0, {self.slots})")
        if self.state_leaves:
            raise ValueError(
                f"a pool's slot state {self.state_leaves} is not in a page "
                "payload")
        row = self.page_tables[slot]
        bound = [int(pid) for pid in row if pid != TRASH_PAGE]
        idx = np.asarray(bound, np.int32)
        layers = []
        for layer in self.layers:
            layers.append({
                name: np.asarray(jax.device_get(buf[idx]))
                for name, buf in layer.items()
            })
        return {
            "n_pages": len(bound),
            "page_size": self.page_size,
            "layers": layers,
        }

    def snapshot_pages(self, slot: int) -> dict:
        """Like :meth:`export_pages`, but DEFERRED: the per-leaf gathers
        are dispatched (``buf[idx]`` — fresh device arrays, nothing
        donated) and returned WITHOUT a device->host copy. The driver
        thread pays only op dispatch; a streaming sender slices and
        ``device_get``s the snapshot chunk by chunk off-thread. The
        snapshot arrays are private copies, so they stay valid across
        later engine steps even though ``self.layers`` is donated."""
        if not 0 <= slot < self.slots:
            raise ValueError(f"slot {slot} outside [0, {self.slots})")
        row = self.page_tables[slot]
        bound = [int(pid) for pid in row if pid != TRASH_PAGE]
        idx = np.asarray(bound, np.int32)
        layers = [
            {name: buf[idx] for name, buf in layer.items()}
            for layer in self.layers
        ]
        return {
            "n_pages": len(bound),
            "page_size": self.page_size,
            "layers": layers,
        }

    def scatter_pages(self, page_ids, layer_rows) -> None:
        """Write foreign page rows into already-allocated pages — the
        incremental half of :meth:`import_pages`. ``layer_rows`` mirrors
        the pool's per-layer leaf dicts with a leading axis of
        ``len(page_ids)``. Must run on the engine driver thread (the
        functional ``self.layers`` swap races concurrent mutators
        otherwise); dtype mismatches raise before any buffer changes.

        Single-device pools take the FUSED path: one jitted dispatch
        updates all ``layers x leaves`` buffers (with donation), so the
        driver block per staged handoff chunk stays a single program
        launch however deep the model is. Sharded pools keep the eager
        per-leaf loop — the update rows must be placed with
        ``kv_sharding`` first, and a handoff import onto a sharded pool
        is already guarded upstream."""
        idx = np.asarray(list(page_ids), np.int32)
        if self.kv_sharding is None:
            rows = [
                {name: np.asarray(src[name], dtype=layer[name].dtype)
                 for name in layer}
                for layer, src in zip(self.layers, layer_rows)
            ]
            self.layers = _fused_page_scatter(self.layers, idx, rows)
            return
        new_layers = []
        for layer, src in zip(self.layers, layer_rows):
            new_layer = {}
            for name, buf in layer.items():
                rows = np.asarray(src[name], dtype=buf.dtype)
                rows = jax.device_put(rows, self.kv_sharding)
                new_layer[name] = buf.at[idx].set(rows)
            new_layers.append(new_layer)
        self.layers = new_layers

    def free_pages(self, page_ids) -> None:
        """Decref a list of pages (abort path of a staged import)."""
        for pid in page_ids:
            self.decref(int(pid))

    def import_pages(self, slot: int, payload: dict) -> list[int]:
        """Write a foreign page payload into fresh pages and bind ``slot``.

        All-or-nothing: raises :class:`InsufficientPages` when the free
        list cannot back the payload (nothing to unwind — the caller
        retries or falls back to local decode on the prefill replica).
        Writes are eager ``.at[pids].set`` scatters into the existing pool
        buffers — the page TABLE stays host numpy and the decode programs
        rebind it exactly as they do for locally-prefilled slots, so no
        new jitted program is introduced. Under a sharded pool the update
        rows are placed with the pool's ``kv_sharding`` first so the
        scatter preserves the kv-head split.
        """
        if not 0 <= slot < self.slots:
            raise ValueError(f"slot {slot} outside [0, {self.slots})")
        if payload["page_size"] != self.page_size:
            raise ValueError(
                f"payload page_size {payload['page_size']} != pool "
                f"page_size {self.page_size}"
            )
        n = int(payload["n_pages"])
        if n > self.pages_per_slot:
            raise ValueError(
                f"{n} payload pages > pages_per_slot {self.pages_per_slot}"
            )
        pages = self.alloc_pages(n)
        if pages is None:
            raise InsufficientPages(
                f"handoff import needs {n} pages, {self.pages_free} free"
            )
        idx = np.asarray(pages, np.int32)
        new_layers = []
        for layer, src in zip(self.layers, payload["layers"]):
            new_layer = {}
            for name, buf in layer.items():
                rows = np.asarray(src[name], dtype=buf.dtype)
                if self.kv_sharding is not None:
                    rows = jax.device_put(rows, self.kv_sharding)
                new_layer[name] = buf.at[idx].set(rows)
            new_layers.append(new_layer)
        self.layers = new_layers
        self.bind(slot, pages)
        return pages


class PrefixCache:
    """Exact-prefix index over immutable full pages, refcounted + LRU.

    An entry is keyed by the prefix it extends and the raw bytes of the
    tokens it adds: ``(node, page tokens)`` for a K/V page, ``("S", node,
    span tokens)`` for an EVA summary page, where ``node`` is the id the
    entry of the prefix before it was given when it was indexed (``ROOT``
    for the empty prefix). Ids are handed out once and never again, so a key
    names exactly one token prefix (by induction on its pages): adopting a
    cached page can never splice a colliding request's KV (a digest could),
    and a lookup hashes each token of the prompt once, where keying every
    page by its whole prefix hashed a 28k-token prompt's 57 MB of prefixes
    (about 80 ms of host time an admission). Value: the physical page id and
    the entry's node. The cache holds its own reference on every indexed
    page; ``match`` adds one per adopting slot, ``evict_for`` drops LRU
    entries (cache reference only — pages still referenced by live slots
    survive until those slots free). A lookup or an insert touches its
    chain from the last entry back to the first, so an entry is always more
    recent than every entry that hangs from it: eviction takes leaves first
    and never leaves behind an entry that no lookup could reach."""

    ROOT = 0

    def __init__(self, pool: PagedKVPool):
        self.pool = pool
        self._entries: OrderedDict[tuple, tuple[int, int]] = OrderedDict()
        self._next_node = self.ROOT + 1
        # Cumulative token counters (the serve_prefix_hit_rate feed).
        self.tokens_matched = 0
        self.tokens_looked_up = 0

    def __len__(self) -> int:
        return len(self._entries)

    @staticmethod
    def _tokens(prompt: np.ndarray, lo: int, hi: int) -> bytes:
        return prompt[lo:hi].astype("<i4").tobytes()

    def _page_key(self, node: int, prompt: np.ndarray, i: int) -> tuple:
        """Key of page ``i`` (0-based) of ``prompt`` after ``node``."""
        ps = self.pool.page_size
        return node, self._tokens(prompt, i * ps, (i + 1) * ps)

    def _summary_key(self, node: int, prompt: np.ndarray, n_tokens: int):
        """Key of the summary page whose summaries end at ``n_tokens``."""
        span = self.pool.window // self.pool.sum_pages
        return "S", node, self._tokens(prompt, n_tokens - span, n_tokens)

    def _add(self, key: tuple, pid: int) -> int:
        """Index ``pid`` under ``key`` unless an entry is there already; the
        entry's node."""
        hit = self._entries.get(key)
        if hit is not None:
            return hit[1]
        self.pool.incref(int(pid))
        node, self._next_node = self._next_node, self._next_node + 1
        self._entries[key] = (int(pid), node)
        return node

    def _touch(self, keys: list) -> None:
        """Make a chain's entries the most recent, its first entry last:
        an entry is then always more recent than every entry that hangs
        from it, and the LRU end is a leaf."""
        for key in reversed(keys):
            self._entries.move_to_end(key)

    def _summary_chain(self, prompt: np.ndarray, n_tokens: int):
        """The keys of the summary chain that ends at ``n_tokens`` (a whole
        number of windows; empty at 0), or None where it is not cached
        whole: what an EVA window's pages hang from."""
        span = self.pool.window // self.pool.sum_pages
        node, keys = self.ROOT, []
        for end in range(span, n_tokens + 1, span):
            key = self._summary_key(node, prompt, end)
            hit = self._entries.get(key)
            if hit is None:
                return None
            node = hit[1]
            keys.append(key)
        return keys

    def _node(self, keys: list) -> int:
        """The node at the end of a chain of keys (``ROOT`` if empty)."""
        return self._entries[keys[-1]][1] if keys else self.ROOT

    def match(self, prompt: np.ndarray, max_pages: int) -> list[int]:
        """Longest chain of cached full pages covering a prefix of
        ``prompt`` (at most ``max_pages``). Each matched page is increffed
        for the adopting slot; entries touch LRU-recency."""
        pages, keys = self._walk(prompt, self.ROOT, 0, max_pages)
        self._touch(keys)
        for pid in pages:
            self.pool.incref(pid)
        return pages

    def _walk(self, prompt, node: int, first: int, n: int):
        """Pages ``first`` .. ``first + n - 1`` of ``prompt`` found in a
        chain from ``node``, up to the first one missing, and their keys."""
        pages: list[int] = []
        keys: list[tuple] = []
        for i in range(first, first + n):
            key = self._page_key(node, prompt, i)
            hit = self._entries.get(key)
            if hit is None:
                break
            pid, node = hit
            pages.append(pid)
            keys.append(key)
        return pages, keys

    def match_eva(self, prompt: np.ndarray, cap_tokens: int):
        """EVA layout: the longest cached prefix of ``prompt`` of at most
        ``cap_tokens`` tokens, as ``(summary pages, window pages)``: every
        summary page of each leading window that is cached whole, then the
        chain of cached full K/V pages of the window after them. Each page
        is increffed for the adopting slot."""
        pool = self.pool
        ps, w = pool.page_size, pool.window
        span = w // pool.sum_pages  # positions one summary page covers
        sums: list[int] = []
        chain: list[tuple] = []
        done, node = 0, self.ROOT
        while (done + 1) * w <= cap_tokens:
            keys, hits, at = [], [], node
            for i in range(pool.sum_pages):
                key = self._summary_key(at, prompt, done * w + (i + 1) * span)
                hit = self._entries.get(key)
                if hit is None:
                    break
                keys.append(key)
                hits.append(hit)
                at = hit[1]
            if len(hits) < pool.sum_pages:
                break
            chain += keys
            sums += [pid for pid, _ in hits]
            done, node = done + 1, at
        base = done * w // ps
        wins, keys = self._walk(prompt, node, base,
                                min(pool.window_pages, cap_tokens // ps - base))
        self._touch(chain + keys)
        for pid in sums + wins:
            pool.incref(pid)
        return sums, wins

    def insert_summaries(self, prompt: np.ndarray, window: int,
                         page_ids) -> None:
        """Index the summary pages of ``prompt``'s finished window
        ``window`` (all of them, each by the prefix its summaries end at),
        behind the cached summaries of the windows before it."""
        w = self.pool.window
        span = w // self.pool.sum_pages
        keys = self._summary_chain(prompt, window * w)
        if keys is None:  # reached by no lookup
            return
        node = self._node(keys)
        for i, pid in enumerate(page_ids):
            keys.append(self._summary_key(
                node, prompt, window * w + (i + 1) * span))
            node = self._add(keys[-1], pid)
        self._touch(keys)

    def record_lookup(self, matched_tokens: int, prompt_tokens: int) -> None:
        self.tokens_matched += matched_tokens
        self.tokens_looked_up += prompt_tokens

    @property
    def hit_rate(self) -> float:
        if self.tokens_looked_up == 0:
            return 0.0
        return self.tokens_matched / self.tokens_looked_up

    def insert(self, prompt: np.ndarray, page_ids, first_page: int = 0) -> None:
        """Index ``prompt``'s full pages (``page_ids[i]`` backs page
        ``first_page + i`` of the prompt; an EVA slot passes its current
        window's pages and the page that window starts at, which hang from
        the cached summaries of the windows before it).
        Already-indexed prefixes keep their existing (shared) page."""
        ps = self.pool.page_size
        n_full = min(len(page_ids), len(prompt) // ps - first_page)
        keys = self._summary_chain(prompt, first_page * ps) if first_page else []
        if keys is None:  # reached by no lookup
            return
        node = self._node(keys)
        for i in range(n_full):
            keys.append(self._page_key(node, prompt, first_page + i))
            node = self._add(keys[-1], page_ids[i])
        self._touch(keys)

    def evict_for(self, pages_wanted: int) -> int:
        """Drop LRU entries until the pool could satisfy ``pages_wanted``
        (or the cache is empty). Returns entries evicted. Only the cache's
        own reference drops — a page shared with a live slot stays
        resident and simply leaves the index."""
        evicted = 0
        while (self.pool.pages_free < pages_wanted) and self._entries:
            _, (pid, _) = self._entries.popitem(last=False)
            self.pool.decref(pid)
            evicted += 1
        return evicted

    def clear(self) -> None:
        while self._entries:
            _, (pid, _) = self._entries.popitem(last=False)
            self.pool.decref(pid)
