"""Paged KV-pool manager — the allocator under the serving engine.

The Pallas ragged kernel (kernels/paged_attention.py) consumes a paged
pool ``[num_kv_heads, num_pages, page_size, head_dim]`` plus per-sequence
block tables; this module owns the layer above it: which pool page
belongs to which live sequence, and what happens when the pool runs dry.
It is the TPU analog of vLLM's BlockSpaceManager and of the reference's
block_multi_head_attention cache manager:

- a free-list allocator over pool pages — page granularity means there is
  no external fragmentation by construction: any request for n free pages
  succeeds iff n pages are free;
- per-sequence block tables (logical page i of a sequence -> pool page),
  grown one page at a time as decode/prefill-chunks cross page boundaries;
- pool page 0 is reserved as the NULL page: padded rows and padded
  block-table slots all point at it, so fixed-shape ragged launches have
  a safe write/read target that never aliases live data;
- **copy-on-write page sharing**: every mapped page carries a refcount.
  ``fork(child, parent, num_tokens)`` maps the parent's pages covering a
  shared prompt prefix into the child's table (refcount + 1, zero data
  movement) — identical system prompts across millions of users occupy
  ONE set of pool pages. A page is copied only when an owner is about to
  APPEND into a page someone else also maps (``prepare_append``): full
  prefix pages are append-free and therefore shared forever; only a
  partially-filled tail page is ever duplicated, right before the first
  divergent append. ``free`` decrements refcounts and recycles a page
  only when the last owner drops it;
- utilization watermarks the scheduler uses for admission control and
  preemption decisions.

Low-bit pools (``dtype=jnp.int8``): K/V pages are stored int8 with one
fp32 scale per (kv head, page) — ``kv_scales``, one (Ks, Vs) pair per
layer, shape [num_kv_heads, num_pages]. The engine quantizes on append
and the ragged kernel dequantizes at the gather (scales ride the
scalar-prefetch channel into SMEM). Shared pages interact with the
scales safely only because shared pages are never appended into without
a CoW copy first: an append can requantize the whole page in place
(running-amax scale growth), which would perturb every other reader —
so the engine restricts int8 prefix sharing to FULL pages, which are
append-free, and ``cow_page`` copies the page's scale row with its data.

Two page groups (``window_layers`` + ``window``): full-attention layers
keep a row's pages for its life, as above; window layers (a query sees
its last ``window`` keys only) hold a BOUNDED set a row in a second group
with its own page count, free list and block tables. A window table is
indexed by the same logical page as the full one; ``prepare_append``
releases the pages that lie wholly under the window of the first token
it is about to append (their slots then name the null page, and the
kernel's windowed walk never reaches them), so a row holds at most
``window + chunk`` tokens of window pages whatever its context.
Admission, preemption with recompute, ``free`` and ``check_invariants``
cover both groups; sharing (fork, pins, export/adopt, the host tier)
knows one kind of page and is refused by name on such a pool.

A latent pool (``latent_row=W``; multi-head latent attention): a token
holds ONE row of ``W`` values a layer, every head's key and value in
compressed form, so a layer's pages are ONE array ``[num_pages,
page_size, W]`` with no kv-head axis and no K/V pair
(``kernels/paged_attention.py::ragged_latent_attention`` reads it).
Allocation, sharing by refcount (fork, pins, copy-on-write), preemption,
``free``, ``rollback`` and ``check_invariants`` are about pages and do
not care what a page holds; what moves page DATA in the (K, V) wire
format (export, adopt, the prefix store, the host tier, a mesh, int8)
is refused by name.

State slots (``state_layers`` + ``state_shape`` + ``conv_shape`` +
``state_slots``; a Gated DeltaNet layer): such a layer keeps no pages. A
row holds ONE slot from admission to finish, cancel or preemption, and
the slot holds the row's recurrent state ``state_shape`` (float32) and
the last pre-convolution inputs ``conv_shape`` of every such layer, at
the row's committed length and no other: ``allocate`` claims it, ``free``
returns it, ``check_invariants`` counts it, and whatever sharing or
rewinding would need the state at an earlier position (fork, pins,
export / adopt, ``rollback``) is refused by name. Slot 0 is the null
slot pad rows name. A row's slot is never read before the row has
written it: the step starts a row whose chunk begins at position 0 from
zeros (so a recomputed row starts afresh whatever its slot held).

The device arrays themselves live in ``kv`` (one (K, V) pair per layer;
one array per layer in a latent pool; a (state, conv) pair of ``[slots,
...]`` arrays per state layer) and are updated *functionally* by the engine's jitted ragged step (the
engine reassigns ``kv`` after each donated call); this class tracks the
host-side ownership metadata plus the eager CoW/scale-reset fixups.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _copy_pages(kv, old_idx, new_idx):
    """Duplicate pool pages ``old_idx`` into ``new_idx`` across every
    layer's (K, V) pair — the device side of copy-on-write. A latent
    pool's layer is one array with the pages leading."""
    return [C.at[new_idx].set(C[old_idx]) if not isinstance(C, tuple)
            else (C[0].at[:, new_idx].set(C[0][:, old_idx]),
                  C[1].at[:, new_idx].set(C[1][:, old_idx])) for C in kv]


_COPY_JIT = None


def _copy_pages_jit(kv, old_idx, new_idx):
    """One jitted (donated on TPU) scatter per CoW batch: only the
    affected page slices move, instead of a full functional copy of the
    pool per page per layer. Re-traces per distinct batch size — CoW
    batches are almost always 1 page."""
    global _COPY_JIT
    if _COPY_JIT is None:
        from ..kernels import _on_tpu
        donate = (0,) if _on_tpu() else ()
        _COPY_JIT = jax.jit(_copy_pages, donate_argnums=donate)
    return _COPY_JIT(kv, old_idx, new_idx)


class PoolExhausted(RuntimeError):
    """Raised when an alloc/extend/CoW needs more free pages than exist."""


class InvariantViolation(AssertionError):
    """Structured ``check_invariants`` failure: the reason plus a pool
    snapshot (refcounts, free-list size, pinned set, offending page ids)
    travel with the exception, so a loadgen soak that dies hundreds of
    virtual steps in is triageable from the artifact alone instead of a
    bare assert with no state. Subclasses ``AssertionError`` so callers
    (and tests) that caught the old asserts keep working."""

    #: flight-recorder post-mortem (the last-N engine/fleet events
    #: leading into the failure) when the pool had a recorder attached
    #: (serving/tracing.py) — None for bare pools
    flight_dump = None

    def __init__(self, reason, snapshot):
        self.reason = reason
        self.snapshot = snapshot
        rcs = snapshot["refcounts"]
        head = dict(list(rcs.items())[:16])
        super().__init__(
            f"{reason} | pool snapshot: used={snapshot['used_pages']}/"
            f"{snapshot['capacity']} free_list={snapshot['free_list_size']} "
            f"offending_pages={snapshot['offending_pages']} "
            f"pinned_chains={len(snapshot['pinned'])} "
            f"nonzero_refcounts={head}"
            f"{'...' if len(rcs) > 16 else ''}")


NULL_PAGE = 0
#: the state slot pad rows name (never held by a sequence)
NULL_SLOT = 0


class PagedKVPool:
    """Refcounted free-list page allocator + per-sequence block tables.

    capacity = ``num_pages - 1`` allocatable pages (page 0 is the null
    page). ``seq_lens`` tracks the token count the engine has committed
    per sequence, so ``pages_needed`` and utilization stay in one place.
    """

    def __init__(self, num_layers, num_kv_heads, head_dim, *, num_pages,
                 page_size, dtype=jnp.float32, high_watermark=0.90,
                 low_watermark=0.50, pinned_page_budget=0, mesh=None,
                 window_layers=(), window=None, window_pages=None,
                 latent_row=None, state_layers=(), state_shape=None,
                 conv_shape=None, state_slots=None):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is reserved)")
        #: layers that keep a recurrent state in slots and no pages
        self.state_layers = tuple(sorted(int(i) for i in state_layers))
        self.state_num_slots = 0
        if self.state_layers:
            if state_slots is None or int(state_slots) < 2:
                raise ValueError("state_layers need state_slots >= 2 "
                                 "(slot 0 is reserved)")
            if state_shape is None or conv_shape is None:
                raise ValueError("state_layers need a state_shape and a "
                                 "conv_shape")
            if window_layers or latent_row is not None or mesh is not None \
                    or jnp.dtype(dtype) == jnp.dtype(jnp.int8):
                raise ValueError(
                    "a pool with state slots is single-device, unquantized "
                    "and keeps (K, V) pages for its other layers: window "
                    "layers, latent rows, a mesh and int8 pages are not "
                    "carried beside recurrent state")
            if self.state_layers[0] < 0 \
                    or self.state_layers[-1] >= num_layers:
                raise ValueError(f"state_layers {self.state_layers} outside "
                                 f"0..{num_layers - 1}")
            self.state_num_slots = int(state_slots)
        # the second page group: which layers' pages live in it, how
        # many keys a query of theirs sees, how many pages it has
        #: values a token holds a layer in a latent pool (the row as the
        #: device lays it out, padding included); None = (K, V) pages
        self.latent_row = None if latent_row is None else int(latent_row)
        if self.latent_row is not None:
            if window_layers or mesh is not None \
                    or jnp.dtype(dtype) == jnp.dtype(jnp.int8):
                raise ValueError(
                    "a latent pool is single-device, unquantized and of "
                    "one page group: window layers, a mesh and int8 pages "
                    "know (K, V) pages")
            if (num_kv_heads, head_dim) != (1, self.latent_row):
                raise ValueError(
                    f"a latent pool has no kv-head axis: pass "
                    f"num_kv_heads 1 and head_dim {self.latent_row}, the "
                    f"row, not ({num_kv_heads}, {head_dim})")
        self.window_layers = tuple(sorted(int(i) for i in window_layers))
        self.window = None
        self.window_num_pages = 0
        if self.window_layers:
            if window is None or int(window) < 1:
                raise ValueError("window_layers need a window >= 1")
            if window_pages is None or int(window_pages) < 2:
                raise ValueError("window_layers need window_pages >= 2 "
                                 "(page 0 is reserved)")
            if self.window_layers[0] < 0 \
                    or self.window_layers[-1] >= num_layers:
                raise ValueError(f"window_layers {self.window_layers} "
                                 f"outside 0..{num_layers - 1}")
            if mesh is not None or jnp.dtype(dtype) == jnp.dtype(jnp.int8):
                raise ValueError(
                    "a pool with window layers is single-device and "
                    "unquantized: a mesh and int8 pages know one group")
            self.window = int(window)
            self.window_num_pages = int(window_pages)
        # tensor-parallel pool: pages (and int8 scale rows) shard over
        # the mesh's model axis on dim 0 — the kv-head axis — so each
        # device holds Hkv/tp heads' pages. The jitted ragged step's
        # sharding inference keeps the updated pool on the same axis,
        # so the split survives across steps without re-placement.
        self.mesh = mesh
        if mesh is not None:
            from ..distributed.gspmd import MODEL_AXIS
            tp = mesh.shape.get(MODEL_AXIS, 1)
            if num_kv_heads % tp:
                raise ValueError(
                    f"PagedKVPool(mesh=...): {num_kv_heads} kv heads do "
                    f"not divide over the {tp}-way model axis")
        if not 0.0 < low_watermark <= high_watermark <= 1.0:
            raise ValueError("watermarks must satisfy 0 < low <= high <= 1")
        self.num_layers = num_layers
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.num_pages = num_pages
        self.page_size = page_size
        self.high_watermark = high_watermark
        self.low_watermark = low_watermark
        self.dtype = jnp.dtype(dtype)
        self.quantized = self.dtype == jnp.dtype(jnp.int8)
        self.kv = []
        for i in range(num_layers):
            if i in self.state_layers:
                n = self.state_num_slots
                self.kv.append((jnp.zeros((n, *state_shape), jnp.float32),
                                jnp.zeros((n, *conv_shape), dtype)))
                continue
            if self.latent_row is not None:
                self.kv.append(jnp.zeros(
                    (num_pages, page_size, self.latent_row), dtype))
                continue
            n = self.window_num_pages if i in self.window_layers \
                else num_pages
            shape = (num_kv_heads, n, page_size, head_dim)
            self.kv.append((jnp.zeros(shape, dtype),
                            jnp.zeros(shape, dtype)))
        # per-(head, page) dequant scales for int8 pools; zero-init so a
        # fresh page's first append sets the scale from its own amax
        # instead of inheriting a fabricated range
        self.kv_scales = None
        if self.quantized:
            sshape = (num_kv_heads, num_pages)
            self.kv_scales = [(jnp.zeros(sshape, jnp.float32),
                               jnp.zeros(sshape, jnp.float32))
                              for _ in range(num_layers)]
        self._repin()   # initial mesh placement (no-op without a mesh)
        # LIFO free list: recently-freed pages are reused first (warm in
        # whatever cache level holds them)
        self._free = list(range(num_pages - 1, NULL_PAGE, -1))
        self._tables: dict[object, list[int]] = {}
        self._lens: dict[object, int] = {}
        #: pool page -> number of owners mapping it: sequences AND pinned
        #: prefix chains both count (0 for free pages)
        self._refcounts = [0] * num_pages
        #: lifetime count of copy-on-write page duplications
        self.cow_copies = 0
        #: pinned prefix chains: chain_id -> (pages, num_tokens), in LRU
        #: order (dict preserves insertion; re-pin/touch re-appends). A
        #: pin is one extra refcount per page — the "rc floor" that lets
        #: a prefix chain outlive its last sequence sharer, up to
        #: ``pinned_page_budget`` pages (LRU-evicted beyond it, and
        #: auto-evicted whenever an allocation would otherwise exhaust
        #: the pool — pinned pages are cache, never demand).
        self.pinned_page_budget = int(pinned_page_budget)
        self._pins: dict[object, tuple[list[int], int]] = {}
        #: pool page -> number of pinned chains mapping it
        self._pin_counts: dict[int, int] = {}
        #: lifetime count of pinned chains evicted (budget or pressure)
        self.pin_evictions = 0
        #: the window group's free list and tables: logical page ->
        #: window-pool page, NULL_PAGE where released or not yet claimed
        self._wfree = list(range(self.window_num_pages - 1, NULL_PAGE, -1))
        self._wtables: dict[object, list[int]] = {}
        #: the state slots' free list and each live sequence's slot
        self._sfree = list(range(self.state_num_slots - 1, NULL_SLOT, -1))
        self._slots: dict[object, int] = {}

    # ---- byte accounting (pool sizing / bench fields) ----
    @staticmethod
    def page_bytes_for(num_layers, num_kv_heads, head_dim, page_size,
                       dtype=jnp.float32) -> int:
        """HBM bytes one pool page costs across all layers, K+V, scale
        rows included for int8 pools."""
        dt = jnp.dtype(dtype)
        data = num_layers * 2 * num_kv_heads * page_size * head_dim \
            * dt.itemsize
        scales = num_layers * 2 * num_kv_heads * 4 \
            if dt == jnp.dtype(jnp.int8) else 0
        return data + scales

    @classmethod
    def pages_for_byte_budget(cls, byte_budget, num_layers, num_kv_heads,
                              head_dim, page_size,
                              dtype=jnp.float32) -> int:
        """Largest ``num_pages`` whose pool fits ``byte_budget`` — how an
        operator sizes fp32 vs int8 pools at the same HBM watermark (the
        ~2x-sequences-per-byte win the int8 pool exists for)."""
        per = cls.page_bytes_for(num_layers, num_kv_heads, head_dim,
                                 page_size, dtype)
        return max(int(byte_budget) // per, 0)

    @property
    def page_bytes(self) -> int:
        """Bytes of one page of the full group (every layer's, without
        window layers); a latent pool's page is one row a token a
        layer."""
        if self.latent_row is not None:
            return self.num_layers * self.page_size * self.latent_row \
                * self.dtype.itemsize
        return self.page_bytes_for(
            self.num_layers - len(self.window_layers)
            - len(self.state_layers), self.num_kv_heads,
            self.head_dim, self.page_size, self.dtype)

    @property
    def window_page_bytes(self) -> int:
        """Bytes of one page of the window group, over its layers."""
        return self.page_bytes_for(len(self.window_layers),
                                   self.num_kv_heads, self.head_dim,
                                   self.page_size, self.dtype)

    @property
    def kv_bytes_per_token(self) -> float:
        """Bytes of pool one cached token occupies (scale rows amortized
        over the page's tokens); tests/test_quantized_path.py holds the
        int8 pool's to under 0.3 of the fp pool's."""
        return self.page_bytes / self.page_size

    @property
    def state_slot_bytes(self) -> int:
        """Bytes of one state slot over the state layers: the recurrent
        state and the convolution's inputs of each."""
        return sum(self.kv[i][0][0].nbytes + self.kv[i][1][0].nbytes
                   for i in self.state_layers)

    @property
    def pool_bytes(self) -> int:
        return self.page_bytes * self.num_pages \
            + self.window_page_bytes * self.window_num_pages \
            + self.state_slot_bytes * self.state_num_slots

    @property
    def model_parallel_degree(self) -> int:
        """Ways the kv-head axis is split over a mesh's model axis."""
        if self.mesh is None:
            return 1
        from ..distributed.gspmd import MODEL_AXIS
        return self.mesh.shape.get(MODEL_AXIS, 1)

    @property
    def kv_bytes_per_token_per_device(self) -> float:
        """Pool bytes one cached token occupies PER DEVICE — the number
        that decides whether a model's KV traffic fits one chip's HBM
        (global bytes / model-parallel degree; the tensor-parallel
        serving win the sharded pool exists for)."""
        return self.kv_bytes_per_token / self.model_parallel_degree

    # ---- capacity ----
    @property
    def capacity(self) -> int:
        return self.num_pages - 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.capacity - len(self._free)

    @property
    def utilization(self) -> float:
        return self.used_pages / self.capacity

    @property
    def window_capacity(self) -> int:
        """Allocatable pages of the window group (0 without one)."""
        return max(self.window_num_pages - 1, 0)

    @property
    def window_pages_used(self) -> int:
        return self.window_capacity - len(self._wfree)

    @property
    def state_capacity(self) -> int:
        """Allocatable state slots (0 without state layers)."""
        return max(self.state_num_slots - 1, 0)

    @property
    def state_slots_used(self) -> int:
        return len(self._slots)

    def slot_of(self, seq_id) -> int:
        """The state slot ``seq_id`` holds."""
        return self._slots[seq_id]

    @staticmethod
    def window_pages_per_row(window, chunk_tokens, page_size) -> int:
        """The most window-group pages one row holds when it appends at
        most ``chunk_tokens`` a step: the pages that cover the first
        new token's window and the chunk, one more for their
        misalignment with page boundaries."""
        return -(-(window - 1 + chunk_tokens) // page_size) + 1

    def window_row_bound(self, chunk_tokens: int) -> int:
        """:meth:`window_pages_per_row` of this pool (0 without window
        layers)."""
        if not self.window_layers:
            return 0
        return self.window_pages_per_row(self.window, chunk_tokens,
                                         self.page_size)

    @property
    def logical_pages(self) -> int:
        """Block-table slots across live sequences — what the pool WOULD
        hold without sharing."""
        return sum(len(t) for t in self._tables.values())

    @property
    def shared_page_fraction(self) -> float:
        """Fraction of logical pages served by a shared physical page:
        ``1 - physical/logical``. 0.0 with no sharing; approaches
        ``(N-1)/N`` when N sequences share one long prefix — the
        admitted-sequences-per-byte win prefix caching exists for."""
        logical = self.logical_pages
        if logical == 0:
            return 0.0
        return 1.0 - self.used_pages / logical

    def page_refcount(self, page: int) -> int:
        return self._refcounts[page]

    def above_high_watermark(self, extra_pages=0) -> bool:
        # pinned-exclusive pages are reclaimable cache, not demand: a
        # pool full of evictable prefixes must not read as pressure (it
        # would pause admission with nothing left to drain it)
        demand = self.used_pages - self.evictable_pages
        return (demand + extra_pages) / self.capacity \
            > self.high_watermark

    def below_low_watermark(self) -> bool:
        demand = self.used_pages - self.evictable_pages
        return demand / self.capacity < self.low_watermark

    def pages_for(self, num_tokens: int) -> int:
        return -(-max(num_tokens, 0) // self.page_size)

    def can_allocate(self, num_tokens: int) -> bool:
        return self.pages_for(num_tokens) <= len(self._free) \
            and self.window_can_hold(num_tokens)

    def window_can_hold(self, num_tokens: int) -> bool:
        """Whether the window group has the pages a NEW row's first
        ``num_tokens`` tokens take (always, without window layers)."""
        return not self.window_layers \
            or self.pages_for(num_tokens) <= len(self._wfree)

    @property
    def pinned_pages(self) -> int:
        """Distinct pool pages held by at least one pinned chain."""
        return len(self._pin_counts)

    @property
    def evictable_pages(self) -> int:
        """Pinned pages whose ONLY owners are pins (no sequence maps
        them) — the pages unpinning would actually recycle."""
        return sum(1 for p, n in self._pin_counts.items()
                   if self._refcounts[p] == n)

    @property
    def available_pages(self) -> int:
        """Free pages plus reclaimable pinned-exclusive pages — what an
        admission decision should compare against (pinned prefixes are
        cache: they yield to demand via LRU eviction)."""
        return len(self._free) + self.evictable_pages

    def _repin(self):
        """Re-place the pool arrays on the mesh sharding after an EAGER
        fixup (CoW copy, recycled-page scale reset): eager ops choose
        their own output sharding, and a drifted placement would re-key
        the engine's jitted ragged step — one silent recompile per
        drift, exactly what the trace-count==1 gate forbids. device_put
        onto the sharding an array already has is free."""
        if self.mesh is None:
            return
        from ..distributed.gspmd import (kv_pool_sharding,
                                         kv_scale_sharding)
        psh = kv_pool_sharding(self.mesh)
        self.kv = [(jax.device_put(K, psh), jax.device_put(V, psh))
                   for K, V in self.kv]
        if self.kv_scales is not None:
            ssh = kv_scale_sharding(self.mesh)
            self.kv_scales = [(jax.device_put(Ks, ssh),
                               jax.device_put(Vs, ssh))
                              for Ks, Vs in self.kv_scales]

    # ---- lifecycle ----
    def _release_pages(self, pages) -> int:
        """Drop one refcount per page; recycle (free-list + int8 scale
        reset) the pages whose refcount hits zero. Returns the number of
        pages actually recycled."""
        recycled = []
        for p in reversed(list(pages)):
            self._refcounts[p] -= 1
            if self._refcounts[p] == 0:
                recycled.append(p)
        self._free.extend(recycled)
        if self.kv_scales is not None and recycled:
            # reset the recycled pages' dequant scales: the append
            # path's running max only ever GROWS a scale, so a recycled
            # page must not hand its next tenant the previous tenant's
            # (possibly much larger) range — that would quantize small
            # new values straight to zero. Pages still mapped elsewhere
            # keep their scales.
            idx = jnp.asarray(recycled, jnp.int32)
            self.kv_scales = [(Ks.at[:, idx].set(0.0),
                               Vs.at[:, idx].set(0.0))
                              for Ks, Vs in self.kv_scales]
            self._repin()
        return len(recycled)

    def _ensure_free(self, n: int, what: str):
        """Evict LRU pinned chains until ``n`` pages are free (or no
        eviction would recycle anything); raises
        :class:`PoolExhausted` on a real shortfall. Pinned prefixes are
        opportunistic cache — they must never turn real demand into an
        exhaustion the scheduler would answer with preemption — but a
        chain whose every page is also mapped by a live sequence frees
        nothing when unpinned, so those survive the shortfall (wiping
        them would cost the whole cache for zero pages)."""
        while n > len(self._free) and self._pins:
            victim = next(
                (cid for cid, (pages, _) in self._pins.items()
                 if any(self._refcounts[p] == self._pin_counts[p]
                        for p in pages)), None)
            if victim is None:
                break
            self.unpin(victim)
            self.pin_evictions += 1
        if n > len(self._free):
            raise PoolExhausted(
                f"{what}: need {n} pages, {len(self._free)} free of "
                f"{self.capacity}")

    def _claim(self, n: int, what: str) -> list[int]:
        self._ensure_free(n, what)
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refcounts[p] = 1
        return pages

    def allocate(self, seq_id, num_tokens: int) -> list[int]:
        """Claim pages for a new sequence of ``num_tokens`` tokens."""
        if seq_id in self._tables:
            raise KeyError(f"sequence {seq_id!r} already has an allocation")
        self._window_need(None, 0, num_tokens)     # raises if short
        if self.state_layers and not self._sfree:
            raise PoolExhausted(f"allocate {seq_id!r}: all "
                                f"{self.state_capacity} state slots held")
        pages = self._claim(self.pages_for(num_tokens),
                            f"allocate {num_tokens} tokens")
        self._tables[seq_id] = pages
        self._lens[seq_id] = num_tokens
        if self.state_layers:
            self._slots[seq_id] = self._sfree.pop()
        if self.window_layers:
            self._wtables[seq_id] = []
            self._window_extend(seq_id, 0, num_tokens)
        return pages

    def fork(self, seq_id, parent_id, num_tokens: int | None = None
             ) -> list[int]:
        """Map the parent's pages covering its first ``num_tokens``
        tokens (default: every FULL page of the parent's committed
        prefix) into a new sequence ``seq_id`` — zero data movement,
        refcount + 1 per shared page. The child starts with
        ``seq_len(seq_id) == num_tokens`` committed tokens; its first
        append into a partially-filled shared tail page triggers a
        copy-on-write duplication (``prepare_append``)."""
        self._one_group("fork")
        if seq_id in self._tables:
            raise KeyError(f"sequence {seq_id!r} already has an allocation")
        parent = self._tables[parent_id]
        if num_tokens is None:
            num_tokens = (self._lens[parent_id] // self.page_size) \
                * self.page_size
        if num_tokens > self._lens[parent_id]:
            raise ValueError(
                f"fork of {num_tokens} tokens exceeds parent "
                f"{parent_id!r}'s committed {self._lens[parent_id]}")
        shared = parent[:self.pages_for(num_tokens)]
        for p in shared:
            self._refcounts[p] += 1
        self._tables[seq_id] = list(shared)
        self._lens[seq_id] = num_tokens
        return list(shared)

    def extend(self, seq_id, new_len: int) -> list[int]:
        """Grow ``seq_id``'s table to cover ``new_len`` tokens; returns the
        newly claimed pages (possibly empty). All-or-nothing on exhaustion.
        """
        table = self._tables[seq_id]
        need = self.pages_for(new_len) - len(table)
        old_len = self._lens[seq_id]
        self._window_need(seq_id, old_len, new_len)     # raises if short
        fresh = self._claim(max(need, 0),
                            f"extend {seq_id!r} to {new_len} tokens")
        table.extend(fresh)
        if self.window_layers:
            self._window_extend(seq_id, old_len, new_len)
        self._lens[seq_id] = max(new_len, old_len)
        return fresh

    # ---- the window group ----
    def _kv_pairs(self, what):
        if self.latent_row is not None:
            raise ValueError(
                f"PagedKVPool.{what}: this pool holds latent rows, one "
                f"array a layer, and the page wire format is (K, V) "
                f"blocks a kv head")

    def _one_group(self, what):
        if self.window_layers:
            raise ValueError(
                f"PagedKVPool.{what}: this pool has window layers, and "
                f"page sharing, export and the host tier know one kind "
                f"of page")
        if self.state_layers:
            raise ValueError(
                f"PagedKVPool.{what}: this pool keeps recurrent state in "
                f"slots, and sharing or moving a row's pages would need "
                f"its state at that position, which no slot holds")

    def _window_first(self, pos: int) -> int:
        """Logical page of the first key the query at ``pos`` sees."""
        return max(pos - self.window + 1, 0) // self.page_size

    def _window_missing(self, seq_id, old_len, new_len):
        """Logical pages the window group must map for ``seq_id`` to
        append ``[old_len, new_len)`` and does not yet."""
        wt = self._wtables.get(seq_id, [])
        return [p for p in range(self._window_first(old_len),
                                 self.pages_for(new_len))
                if p >= len(wt) or wt[p] == NULL_PAGE]

    def _window_need(self, seq_id, old_len, new_len):
        if not self.window_layers:
            return
        need = len(self._window_missing(seq_id, old_len, new_len))
        if need > len(self._wfree):
            raise PoolExhausted(
                f"window group: {seq_id!r} to {new_len} tokens needs "
                f"{need} pages, {len(self._wfree)} free of "
                f"{self.window_capacity}")

    def _window_extend(self, seq_id, old_len, new_len):
        wt = self._wtables[seq_id]
        wt.extend([NULL_PAGE] * (self.pages_for(new_len) - len(wt)))
        for p in self._window_missing(seq_id, old_len, new_len):
            wt[p] = self._wfree.pop()

    def _window_release(self, seq_id, old_len):
        """Give back the pages that lie wholly under the window of the
        token at ``old_len``, the first that will be appended next: no
        later query sees them."""
        wt = self._wtables[seq_id]
        for p in range(min(self._window_first(old_len), len(wt))):
            if wt[p] != NULL_PAGE:
                self._wfree.append(wt[p])
                wt[p] = NULL_PAGE

    def window_block_table(self, seq_id) -> list[int]:
        return list(self._wtables[seq_id])

    def padded_window_table(self, seq_id, pages: int) -> list[int]:
        """The window group's table at a fixed launch width; released
        and unclaimed slots name the null page."""
        wt = self._wtables[seq_id]
        if len(wt) > pages:
            raise ValueError(
                f"{seq_id!r} spans {len(wt)} pages > launch width {pages}")
        return wt + [NULL_PAGE] * (pages - len(wt))

    def prepare_append(self, seq_id, new_len: int) -> int:
        """Make ``[seq_len, new_len)`` safely writable for ``seq_id``:
        claim fresh pages past the table's end AND copy-on-write every
        SHARED page the append range touches (a shared page may have
        other readers — dup it before the first divergent write).
        Commits ``seq_len = new_len``. All-or-nothing on exhaustion
        (fresh + CoW pages are counted up front). Returns the number of
        CoW copies performed (the metrics counter's increment)."""
        table = self._tables[seq_id]
        old_len = self._lens[seq_id]
        if new_len < old_len:
            raise ValueError(f"append cannot shrink {seq_id!r}: "
                             f"{old_len} -> {new_len}")
        if self.window_layers:
            # first, so that a row's own released pages can serve it
            self._window_release(seq_id, old_len)
            self._window_need(seq_id, old_len, new_len)
        need_fresh = max(self.pages_for(new_len) - len(table), 0)
        first = old_len // self.page_size
        last = self.pages_for(new_len)          # exclusive logical bound
        def _shared():
            return [i for i in range(first, min(last, len(table)))
                    if self._refcounts[table[i]] > 1]

        shared = _shared()
        # all-or-nothing: fresh + CoW pages are priced together, with
        # LRU pinned chains evicted first if that is what it takes
        self._ensure_free(
            need_fresh + len(shared),
            f"append {seq_id!r} to {new_len} tokens: need "
            f"{need_fresh} fresh + {len(shared)} CoW pages")
        # eviction may have dropped a pin's refcount on a page in the
        # write range — recompute so a now-exclusive page is written in
        # place instead of CoW'd into a leak
        shared = _shared()
        olds, news = [], []
        for i in shared:
            old = table[i]
            new = self._claim(1, f"CoW for {seq_id!r}")[0]
            self._refcounts[old] -= 1
            table[i] = new
            olds.append(old)
            news.append(new)
        if olds:
            # one batched device copy for the whole CoW set: page data
            # and (for int8 pools) the pages' scale columns travel
            # together — a duplicated page must dequantize identically
            old_idx = jnp.asarray(olds, jnp.int32)
            new_idx = jnp.asarray(news, jnp.int32)
            self.kv = _copy_pages_jit(self.kv, old_idx, new_idx)
            if self.kv_scales is not None:
                self.kv_scales = [
                    (Ks.at[:, new_idx].set(Ks[:, old_idx]),
                     Vs.at[:, new_idx].set(Vs[:, old_idx]))
                    for Ks, Vs in self.kv_scales]
            self._repin()
            self.cow_copies += len(olds)
        self.extend(seq_id, new_len)
        self._lens[seq_id] = new_len
        return len(olds)

    def free(self, seq_id) -> int:
        """Drop every page mapping the sequence owns; a page is recycled
        (returned to the free list) only when its refcount hits zero —
        pages a pinned prefix chain also holds survive at the pin's rc
        floor. Returns the number of pages actually recycled."""
        pages = self._tables.pop(seq_id)
        self._lens.pop(seq_id, None)
        self._wfree.extend(p for p in self._wtables.pop(seq_id, ())
                           if p != NULL_PAGE)
        if seq_id in self._slots:
            self._sfree.append(self._slots.pop(seq_id))
        return self._release_pages(pages)

    # ---- pinned prefix chains (LRU page cache over the pool) ----
    def pin(self, chain_id, seq_id, num_tokens: int) -> bool:
        """Pin the pages covering ``seq_id``'s first ``num_tokens``
        committed tokens (must be page-aligned: only FULL pages are
        append-free and therefore safe to outlive their writers) under
        ``chain_id``. The pin takes one refcount per page, so the chain
        survives the sequence's ``free`` — repeated cold prompts re-fork
        instead of re-prefilling. Re-pinning an existing chain refreshes
        its LRU recency. Returns False (and pins nothing) when the
        budget is 0 or the chain alone exceeds it."""
        self._one_group("pin")
        if num_tokens % self.page_size != 0:
            raise ValueError(
                f"pinned chains must be page-aligned: {num_tokens} "
                f"tokens over page_size {self.page_size}")
        n_pages = num_tokens // self.page_size
        if n_pages < 1 or n_pages > self.pinned_page_budget:
            return False
        if self._lens.get(seq_id, -1) < num_tokens:
            raise ValueError(
                f"pin of {num_tokens} tokens exceeds {seq_id!r}'s "
                f"committed {self._lens.get(seq_id)}")
        if chain_id in self._pins:
            self.unpin(chain_id)                 # refresh (LRU + pages)
        pages = self._tables[seq_id][:n_pages]
        # LRU budget: evict oldest chains until this one fits
        while self.pinned_pages + n_pages > self.pinned_page_budget \
                and self._pins:
            self.unpin(next(iter(self._pins)))
            self.pin_evictions += 1
        for p in pages:
            self._refcounts[p] += 1
            self._pin_counts[p] = self._pin_counts.get(p, 0) + 1
        self._pins[chain_id] = (list(pages), num_tokens)
        return True

    def unpin(self, chain_id) -> int:
        """Drop a pinned chain's refcounts; recycles pages no sequence
        maps anymore. Returns the number of pages recycled."""
        pages, _ = self._pins.pop(chain_id)
        for p in pages:
            self._pin_counts[p] -= 1
            if self._pin_counts[p] == 0:
                del self._pin_counts[p]
        return self._release_pages(pages)

    def is_pinned(self, chain_id) -> bool:
        return chain_id in self._pins

    # ---- persistence (io/persist.py prefix store) ----
    def config(self) -> dict:
        """Geometry/dtype signature a persisted prefix chain must match
        to be restorable — the two sides of a restore-mismatch error."""
        return {"num_layers": self.num_layers,
                "num_kv_heads": self.num_kv_heads,
                "head_dim": self.head_dim,
                "page_size": self.page_size,
                "dtype": str(self.dtype)}

    def export_pinned(self) -> list:
        """Serialize every pinned chain's page data, LRU order (oldest
        first, so a restore under a smaller budget keeps the hottest
        chains last-written): per chain, per layer, the K/V page blocks
        ``[Hkv, n_pages, page_size, head_dim]`` (plus the per-(head,
        page) scale columns for int8 pools) as host numpy arrays."""
        self._kv_pairs("export_pinned")
        out = []
        for cid, (pages, num_tokens) in self._pins.items():
            idx = jnp.asarray(pages, jnp.int32)
            layers = []
            for li, (K, V) in enumerate(self.kv):
                ent = {"K": np.asarray(K[:, idx]),
                       "V": np.asarray(V[:, idx])}
                if self.kv_scales is not None:
                    Ks, Vs = self.kv_scales[li]
                    ent["Ks"] = np.asarray(Ks[:, idx])
                    ent["Vs"] = np.asarray(Vs[:, idx])
                layers.append(ent)
            out.append({"chain_id": cid, "num_tokens": num_tokens,
                        "layers": layers})
        return out

    def export_chain(self, chain_id) -> list:
        """Serialize ONE pinned chain's page data (the per-chain slice
        of :meth:`export_pinned`) — what the fleet prefix cache
        (serving/fabric.py) publishes after a pin, without paying a
        device read of every other chain."""
        pages, _ = self._pins[chain_id]
        return self._read_pages(pages)

    def _read_pages(self, pages) -> list:
        """Device -> host read of pool pages as one
        ``[Hkv, len(pages), ps, d]`` block per layer (K/V + int8 scale
        columns) — the HostKVArena ``layers`` format, which makes spill
        buffers, fleet transfers, and prefix publishes one wire
        format."""
        self._kv_pairs("_read_pages")
        idx = jnp.asarray(pages, jnp.int32)
        out = []
        for li, (K, V) in enumerate(self.kv):
            ent = {"K": np.asarray(K[:, idx]),
                   "V": np.asarray(V[:, idx])}
            if self.kv_scales is not None:
                Ks, Vs = self.kv_scales[li]
                ent["Ks"] = np.asarray(Ks[:, idx])
                ent["Vs"] = np.asarray(Vs[:, idx])
            out.append(ent)
        return out

    # ---- disaggregated serving (serving/fabric.py) ----
    def export_pages(self, seq_id, num_tokens=None) -> tuple:
        """Read the pages covering ``seq_id``'s first ``num_tokens``
        committed tokens (default: all of them) as host numpy blocks —
        the prefill side of a KV handoff. Returns ``(num_tokens,
        layers)`` in the arena/adopt wire format. Read-only: refcounts,
        tables, and sharing are untouched."""
        self._one_group("export_pages")
        self._kv_pairs("export_pages")
        if num_tokens is None:
            num_tokens = self._lens[seq_id]
        if num_tokens > self._lens[seq_id]:
            raise ValueError(
                f"export of {num_tokens} tokens exceeds {seq_id!r}'s "
                f"committed {self._lens[seq_id]}")
        pages = self._tables[seq_id][:self.pages_for(num_tokens)]
        bad = [p for p in pages if p < 0]
        if bad:
            raise PoolExhausted(
                f"export of {seq_id!r}: {len(bad)} pages are not "
                f"HBM-resident (restore before extracting)")
        return num_tokens, self._read_pages(pages)

    def adopt_sequence(self, seq_id, num_tokens, layers) -> list:
        """Land transferred KV pages as a NEW fully-resident sequence —
        the decode side of a KV handoff (inverse of
        :meth:`export_pages`): claim fresh pages, write each layer's
        blocks (int8 scale columns included), and commit ``num_tokens``.
        All-or-nothing: :class:`PoolExhausted` when the pages cannot be
        claimed even after LRU pin eviction. The two-tier pool overrides
        this to stage into the host arena instead (the sequence lands
        PARKED and rides the prefetch/restore path into HBM)."""
        self._one_group("adopt_sequence")
        self._kv_pairs("adopt_sequence")
        if seq_id in self._tables:
            raise KeyError(f"sequence {seq_id!r} already has an allocation")
        if len(layers) != self.num_layers:
            raise ValueError(
                f"adopted sequence has {len(layers)} layers, pool has "
                f"{self.num_layers}")
        n_pages = self.pages_for(num_tokens)
        want = (self.num_kv_heads, n_pages, self.page_size, self.head_dim)
        for li, ent in enumerate(layers):
            if tuple(np.asarray(ent["K"]).shape) != want:
                raise ValueError(
                    f"adopted sequence layer {li}: block shape "
                    f"{tuple(np.asarray(ent['K']).shape)} != pool {want}")
        pages = self._claim(n_pages, f"adopt {seq_id!r} "
                                     f"({num_tokens} tokens)")
        idx = jnp.asarray(pages, jnp.int32)
        self.kv = [(K.at[:, idx].set(jnp.asarray(ent["K"], self.dtype)),
                    V.at[:, idx].set(jnp.asarray(ent["V"], self.dtype)))
                   for (K, V), ent in zip(self.kv, layers)]
        if self.kv_scales is not None:
            self.kv_scales = [
                (Ks.at[:, idx].set(jnp.asarray(ent["Ks"], jnp.float32)),
                 Vs.at[:, idx].set(jnp.asarray(ent["Vs"], jnp.float32)))
                for (Ks, Vs), ent in zip(self.kv_scales, layers)]
        self._repin()
        self._tables[seq_id] = list(pages)
        self._lens[seq_id] = num_tokens
        return list(pages)

    # single-tier pools have no host tier, so an adopted sequence is
    # already fully resident: the scheduler's parked-admission branch
    # (which fires for ANY sequence that owns a table while waiting)
    # sees zero spilled pages and a free no-op restore
    def spilled_page_count(self, seq_id) -> int:
        return 0

    def restore_headroom(self, seq_id) -> int:
        return self.available_pages

    def restore_sequence(self, seq_id) -> int:
        return 0

    def restore_pinned_chain(self, chain_id, num_tokens, layers) -> bool:
        """Materialize a persisted chain back into the pool as a pinned
        prefix: claim fresh pages, write each layer's K/V blocks (and
        int8 scale columns) into them, and register the pin — the warm-
        restart inverse of :meth:`export_pinned`. Returns False (and
        touches nothing) when the chain cannot fit (zero budget, chain
        alone over budget, or no free pages even after LRU eviction);
        raises ``ValueError`` on geometry violations (the engine wraps
        shape/dtype drift in its structured mismatch error before this
        layer ever sees it)."""
        self._one_group("restore_pinned_chain")
        self._kv_pairs("restore_pinned_chain")
        if num_tokens % self.page_size != 0:
            raise ValueError(
                f"restored chains must be page-aligned: {num_tokens} "
                f"tokens over page_size {self.page_size}")
        n_pages = num_tokens // self.page_size
        if n_pages < 1 or n_pages > self.pinned_page_budget:
            return False
        if len(layers) != self.num_layers:
            raise ValueError(
                f"restored chain has {len(layers)} layers, pool has "
                f"{self.num_layers}")
        # feasibility BEFORE any mutation: eviction only ever recycles
        # pin-exclusive pages, so free + evictable bounds what a restore
        # can claim — deciding now keeps the touches-nothing contract
        # honest for post-init callers on a busy pool (evicting first
        # and then failing would have destroyed the warm cache for
        # nothing; at engine construction free pages alone suffice)
        if n_pages > len(self._free) + self.evictable_pages:
            return False
        if chain_id in self._pins:
            self.unpin(chain_id)
        while self.pinned_pages + n_pages > self.pinned_page_budget \
                and self._pins:
            self.unpin(next(iter(self._pins)))
            self.pin_evictions += 1
        # _claim's _ensure_free evicts further LRU chains if the budget
        # evictions freed too little; the upfront bound guarantees it
        # succeeds
        pages = self._claim(n_pages, f"restore pinned chain ({n_pages} "
                                     f"pages)")
        idx = jnp.asarray(pages, jnp.int32)
        new_kv = []
        for li, ((K, V), ent) in enumerate(zip(self.kv, layers)):
            k = jnp.asarray(ent["K"], self.dtype)
            v = jnp.asarray(ent["V"], self.dtype)
            want = (self.num_kv_heads, n_pages, self.page_size,
                    self.head_dim)
            if tuple(k.shape) != want or tuple(v.shape) != want:
                # roll the claim back before raising: a failed restore
                # must leave the pool exactly as it found it
                for p in pages:
                    self._refcounts[p] = 0
                self._free.extend(reversed(pages))
                raise ValueError(
                    f"restored chain layer {li}: block shape "
                    f"{tuple(k.shape)} != pool {want}")
            new_kv.append((K.at[:, idx].set(k), V.at[:, idx].set(v)))
        self.kv = new_kv
        if self.kv_scales is not None:
            self.kv_scales = [
                (Ks.at[:, idx].set(jnp.asarray(ent["Ks"], jnp.float32)),
                 Vs.at[:, idx].set(jnp.asarray(ent["Vs"], jnp.float32)))
                for (Ks, Vs), ent in zip(self.kv_scales, layers)]
        self._repin()
        for p in pages:
            self._pin_counts[p] = self._pin_counts.get(p, 0) + 1
        self._pins[chain_id] = (list(pages), num_tokens)
        return True

    def touch_pin(self, chain_id):
        """Refresh a chain's LRU recency (a probe hit keeps it hot)."""
        ent = self._pins.pop(chain_id)
        self._pins[chain_id] = ent

    def fork_pinned(self, seq_id, chain_id, num_tokens: int) -> list[int]:
        """Map a pinned chain's pages covering ``num_tokens`` tokens
        into a new sequence — the cold-prompt analog of :meth:`fork`
        (zero data movement, refcount + 1 per page). Touches the
        chain's LRU recency."""
        self._one_group("fork_pinned")
        if seq_id in self._tables:
            raise KeyError(f"sequence {seq_id!r} already has an allocation")
        pages, pinned_tokens = self._pins[chain_id]
        if num_tokens > pinned_tokens:
            raise ValueError(
                f"fork of {num_tokens} tokens exceeds the chain's "
                f"pinned {pinned_tokens}")
        shared = pages[:self.pages_for(num_tokens)]
        for p in shared:
            self._refcounts[p] += 1
        self._tables[seq_id] = list(shared)
        self._lens[seq_id] = num_tokens
        self.touch_pin(chain_id)
        return list(shared)

    # ---- queries ----
    def __contains__(self, seq_id) -> bool:
        return seq_id in self._tables

    def block_table(self, seq_id) -> list[int]:
        return list(self._tables[seq_id])

    def seq_len(self, seq_id) -> int:
        return self._lens[seq_id]

    def set_seq_len(self, seq_id, n: int):
        if self.pages_for(n) > len(self._tables[seq_id]):
            raise ValueError(
                f"length {n} exceeds the {len(self._tables[seq_id])} pages "
                f"owned by {seq_id!r}; call extend() first")
        self._lens[seq_id] = n

    def rollback(self, seq_id, new_len: int):
        """Shrink a sequence's committed length after a speculative
        over-append (serving/spec_decode.py): the pages stay OWNED — the
        rejected tail's K/V slots are garbage the next append simply
        overwrites, and attention never reads past the committed length
        — only the attention/append cursor moves back. Freeing the tail
        pages instead would churn the allocator every rejected round for
        pages the sequence is about to grow back into."""
        cur = self._lens[seq_id]
        if new_len > cur:
            raise ValueError(
                f"rollback cannot grow {seq_id!r}: {cur} -> {new_len}")
        if new_len < 0:
            raise ValueError(f"negative rollback length {new_len}")
        if self.state_layers and new_len < cur:
            raise ValueError(
                f"rollback of {seq_id!r} from {cur} to {new_len}: its state "
                f"slot holds the recurrent state at {cur} only, and an "
                f"earlier one is not kept")
        if self.window_layers and any(
                p == NULL_PAGE for p in self._wtables[seq_id][
                    self._window_first(new_len):self.pages_for(new_len)]):
            raise ValueError(
                f"rollback of {seq_id!r} to {new_len} reaches under its "
                f"window: those pages were released")
        self._lens[seq_id] = new_len

    def padded_block_table(self, seq_id, pages: int) -> list[int]:
        """Block table padded with NULL_PAGE to a fixed launch width."""
        table = self._tables[seq_id]
        if len(table) > pages:
            raise ValueError(
                f"{seq_id!r} owns {len(table)} pages > launch width {pages}")
        return table + [NULL_PAGE] * (pages - len(table))

    def live_sequences(self):
        return list(self._tables)

    def snapshot(self, offending_pages=()) -> dict:
        """Host-side pool state for failure triage (no device reads):
        nonzero refcounts, free-list size, pinned chain ids, sequence
        count, and the page ids the caller found offending. This is what
        :class:`InvariantViolation` carries out of a soak run."""
        return {
            "capacity": self.capacity,
            "used_pages": self.used_pages,
            "free_list_size": len(self._free),
            "refcounts": {p: rc for p, rc in enumerate(self._refcounts)
                          if rc},
            "pinned": list(self._pins),
            "pin_counts": dict(self._pin_counts),
            "num_sequences": len(self._tables),
            "offending_pages": sorted(set(offending_pages)),
            "window_used_pages": self.window_pages_used,
            "window_free_list_size": len(self._wfree),
            "state_slots_used": self.state_slots_used,
            "state_free_list_size": len(self._sfree),
        }

    def _invariant_fail(self, reason, pages=()):
        """Raise :class:`InvariantViolation` carrying a :meth:`snapshot`
        (and the flight recorder's last-N context when one is attached)
        — shared by :meth:`check_invariants` and the two-tier pool's
        residency audit (serving/kv_tier.py)."""
        err = InvariantViolation(reason, self.snapshot(pages))
        # always-on flight recorder (serving/tracing.py): the engine
        # back-references its recorder on the pool so a failing
        # audit ships the last-N steps of context WITH the exception
        # — a soak that dies mid-storm is triageable from the
        # artifact alone. A bare pool (unit tests) has no recorder.
        fr = getattr(self, "flight_recorder", None)
        if fr is not None:
            ctr = getattr(self, "flight_dump_counter", None)
            if ctr is not None:
                ctr.inc()
            err.flight_dump = fr.dump("invariant_violation",
                                      violation=reason)
        raise err

    def _resident_table(self, t):
        """Block-table entries that name RESIDENT pool pages — the hook
        the two-tier pool overrides (host-sentinel entries live in the
        arena and are audited by its own residency pass)."""
        return t

    def check_invariants(self):
        """Debug/test/soak hook: refcount/free-list/table consistency.

        - every mapped page's refcount equals the number of owners
          mapping it — sequence tables AND pinned chains both count —
          (and is therefore >= 1);
        - every free page has refcount 0 and no free page is mapped;
        - distinct physical pages in use + free pages == capacity;
        - the null page is never mapped and never on the free list;
        - pinned bookkeeping (_pin_counts) matches the pinned chains
          and stays within the pinned-page budget.

        A failure raises :class:`InvariantViolation` carrying a
        :meth:`snapshot` (refcounts, free-list size, pinned set, the
        offending page ids) instead of a bare assert.
        """
        fail = self._invariant_fail

        mapped: dict[int, int] = {}
        for sid, t in self._tables.items():
            seen_in_table = set()
            for p in self._resident_table(t):
                if p in seen_in_table:
                    fail(f"table {sid!r} maps pool page {p} twice", [p])
                seen_in_table.add(p)
                mapped[p] = mapped.get(p, 0) + 1
        pin_counts: dict[int, int] = {}
        for cid, (pages, num_tokens) in self._pins.items():
            if num_tokens % self.page_size != 0:
                fail(f"pinned chain {cid!r} is not page-aligned "
                     f"({num_tokens} tokens)", pages)
            for p in pages:
                mapped[p] = mapped.get(p, 0) + 1
                pin_counts[p] = pin_counts.get(p, 0) + 1
        if pin_counts != self._pin_counts:
            drift = set(pin_counts.items()) ^ set(self._pin_counts.items())
            fail(f"pin accounting drift: {pin_counts} != "
                 f"{self._pin_counts}", [p for p, _ in drift])
        if len(pin_counts) > max(self.pinned_page_budget, 0):
            fail(f"{len(pin_counts)} pinned pages exceed the "
                 f"pinned-page budget {self.pinned_page_budget}",
                 pin_counts)
        if NULL_PAGE in mapped:
            fail("null page leaked into a table", [NULL_PAGE])
        if NULL_PAGE in self._free:
            fail("null page on the free list", [NULL_PAGE])
        bad_rc = [p for p, owners in mapped.items()
                  if self._refcounts[p] != owners]
        if bad_rc:
            p = bad_rc[0]
            fail(f"page {p}: refcount {self._refcounts[p]} != "
                 f"{mapped[p]} owners", bad_rc)
        free_set = set(self._free)
        if len(free_set) != len(self._free):
            dups = [p for p in free_set if self._free.count(p) > 1]
            fail("free list has duplicates", dups)
        if free_set & set(mapped):
            fail("page both mapped and free", free_set & set(mapped))
        bad_free = [p for p in self._free if self._refcounts[p] != 0]
        if bad_free:
            fail(f"free page {bad_free[0]} has refcount "
                 f"{self._refcounts[bad_free[0]]}", bad_free)
        if len(mapped) + len(self._free) != self.capacity:
            fail(f"page accounting leak: {len(mapped)} mapped + "
                 f"{len(self._free)} free != capacity {self.capacity}")
        if self.used_pages != len(mapped):
            fail(f"used_pages {self.used_pages} != {len(mapped)} "
                 f"mapped pages")
        if self.window_layers:
            self._check_window_invariants()
        if self.state_layers:
            self._check_state_invariants()
        return True

    def _check_state_invariants(self):
        """The state slots: every live sequence holds exactly one, no two
        hold the same, none holds the null slot, a held slot is not
        free; held + free == capacity."""
        fail = self._invariant_fail
        if set(self._slots) != set(self._tables):
            fail("state slots and page tables name different sequences")
        held = list(self._slots.values())
        if len(set(held)) != len(held):
            fail(f"a state slot is held twice: {sorted(held)}")
        free = set(self._sfree)
        if len(free) != len(self._sfree):
            fail("state slot free list has duplicates")
        if NULL_SLOT in free or NULL_SLOT in held:
            fail("null state slot held or free")
        if free & set(held):
            fail(f"state slots both held and free: {sorted(free & set(held))}")
        if len(held) + len(free) != self.state_capacity:
            fail(f"state slot leak: {len(held)} held + {len(free)} free != "
                 f"capacity {self.state_capacity}")

    def _check_window_invariants(self):
        """The window group: a page is held by one row or free, never
        both and never the null page; every row maps exactly the pages
        from its next token's window to its length's end that it was
        granted (claimed pages may run ahead of the committed length,
        never behind the window); held + free == capacity."""
        fail = self._invariant_fail
        if set(self._wtables) != set(self._tables):
            fail("window tables and full tables name different sequences")
        held = {}
        for sid, wt in self._wtables.items():
            first = self._window_first(self._lens[sid])
            for i, p in enumerate(wt):
                if p == NULL_PAGE:
                    if first <= i < self.pages_for(self._lens[sid]):
                        fail(f"window table {sid!r}: logical page {i} "
                             f"inside the window is not mapped")
                    continue
                if p in held:
                    fail(f"window page {p} held by {held[p]!r} and "
                         f"{sid!r}", [p])
                held[p] = sid
        free = set(self._wfree)
        if len(free) != len(self._wfree):
            fail("window free list has duplicates")
        if NULL_PAGE in free or NULL_PAGE in held:
            fail("null page in the window group", [NULL_PAGE])
        if free & set(held):
            fail("window page both held and free", free & set(held))
        if len(held) + len(free) != self.window_capacity:
            fail(f"window page leak: {len(held)} held + {len(free)} free "
                 f"!= capacity {self.window_capacity}")


__all__ = ["InvariantViolation", "PagedKVPool", "PoolExhausted",
           "NULL_PAGE", "NULL_SLOT"]
