"""Paged KV cache (reference analogue: vLLM's PagedAttention, SOSP '23).

The cache for every layer is ONE preallocated JAX array shaped
``[num_pages, page_size, kv_heads * head_dim]`` (one for K, one for V):
a token's K (or V) is one row, its heads side by side as the projection
wrote them. That is the one shape every reader and writer of a pool
takes: the paged kernel blocks it as it is, a new token's row is written
where it lies (``ops.paged_attention.scatter_kv_slots``), and its two
minor dimensions tile on the TPU with next to no padding (GPT-2 XL's
1600 features on 1664 lanes; a ``[.., 25, 64]`` minor pair pads by a
third, and every program relaid it twice a step: PERF.md, PR 27).
Sequences own pages through a *block table* — an ordered list of page
ids — so a sequence's logical position ``p`` lives at flat slot
``table[p // page_size] * page_size + p % page_size``. Growing a
sequence by one token allocates at most one page; freeing returns the
pages to a stack. Nothing is ever reallocated or compacted, which is
the property the TPU decode step needs: the jitted program sees the
same cache buffers every iteration and only the (tiny, host-built)
block tables change. The engine's three programs are given the pools
*donated*: they write the new rows into the buffers they received and
hand those back, so ``k`` and ``v`` are rebound after every call and an
array read out of them before a step is deleted after it.

A latent-attention model (``latent_row``) has one pool a layer, not a K
and a V: a token's row holds the compressed latent its keys and values
are both computed from, so ``v`` is empty and everything else here, which
deals in pages and not in what a row holds, is unchanged. Where its
attention chooses the rows it reads (``index_row``), ``v`` holds a second
pool a layer, the indexer's keys, ``index_row`` wide: two pools of unequal
row width under one block table, so a page shared, freed or recomputed is
a page of both.

Page 0 is reserved as *scratch*: it is never handed to a sequence, and
every padded slot in a bucketed prefill or dummy row in a padded decode
batch writes there. Garbage lands only in page 0, so real pages are
never polluted by static-shape padding.

Pages are REFCOUNTED so a prefix cache can share prompt pages across
sequences copy-on-write-style: ``allocate_shared`` grafts already-filled
pages into a new block table by bumping their refcount, and ``free``
only surrenders a page once its last owner releases it. A page whose
refcount drops to 0 is offered to an optional *retainer* (the prefix
cache) before returning to the free list; retained pages stay
reclaimable and are evicted LRU when an allocation would otherwise
fail, so caching never reduces usable capacity.

Host-side bookkeeping (block tables, free list, refcounts) is plain
Python — it's O(pages touched) per step and never traced.

**The tables as a program takes them are kept, not rebuilt.** Beside the
lists, which say what a sequence owns and gives back, the manager keeps
each kind's block tables as one array ``int32[rows, columns]``: a
sequence's pages in its *row* (taken and given back with its pages; row 0
is no sequence's, and a padding row of a batch names it), logical page
``c`` in column ``c``, scratch (0) in every other column. The array is
written where the lists are (``_grant`` on the right, :meth:`slide` on the
left, :meth:`free`), so :meth:`table_array` is a gather of rows, a batch's
slots one indexing (:meth:`slots`), and a reader that kept what it was
given last can ask whether its rows have changed since
(:meth:`table_version`): a row changes once every ``page_size`` positions.

**Pools by kind of layer.** A model whose layers are not all alike
(``layer_windows``) has two kinds of pool under this one manager, and a
sequence one block table a kind. A *full* layer's pool is everything
above: ``num_pages`` pages, a table that grows with the sequence. A
*window* layer (its query sees the ``window`` newest positions only)
has a pool of ``window_pages`` pages of its own and a *sliding* table:
``(first, pages)``, the sequence's logical pages ``first ..
first + len(pages) - 1``. :meth:`slide` is called before every program
that writes a sequence's positions ``[lo, hi)``: it takes pages on the
right up to the one that holds ``hi - 1`` and gives back to the window
free list every page wholly left of position ``lo - window + 1``, the
oldest one any of the call's queries sees. So a sequence owns at most
``window_seq_pages`` (``ceil(window / page_size) + 1``) window pages
between calls, whatever its length, and ``window_burst_pages`` more
while a chunk of ``window_burst`` tokens is written (all of a chunk's
rows have to be in the pool before any attends). A window table is
handed to a program as wide as the full one, scratch (0) in the columns
it has given back: the paged kernel starts at the window's first page
and never reads them, and a whole prompt's rows left of the window are
written to the scratch page. Admission keeps one seat of
``window_seq_pages`` a sequence and one burst for the pool, so
:meth:`slide` never fails and only the full pools can run out:
``allocate`` succeeds if both kinds have room. A model of one kind has
exactly the pools and tables described above. Pages of a window pool are
never shared: a prompt's are gone by the time another could use them,
so the prefix cache and the KV hand-off refuse such a model.

**Seats, and layers that keep a state.** A layer need not hold keys and
values at all: a short convolution keeps the newest rows of its input and
nothing else, the same few rows however long the sequence. Such a layer
(``state_shapes``) has no pool of pages here but one *state array*
``[seats + 1, *shape]``, ``state[i]``, and a sequence one *seat* in all of
them: row ``seat`` is its state, read and written by the programs as the
pools are (donated, rebound after every call). Seat 0 is scratch, as page
0 is: a padded decode batch's dummy rows read and write it. A seat is
taken with a sequence's pages (:meth:`allocate` fails when either runs
out) and given back with them (:meth:`free`); nothing clears it in
between, so the first program that writes a sequence's state (the one
that holds its position 0) starts from zeros and not from what the seat
holds. A preempted sequence loses its seat with its pages and recomputes
both. The state at the end of a prefix is nowhere in the prefix's pages,
so the prefix cache and the KV hand-off refuse such a model too. The seat
map is kept whenever ``seats`` is given, state arrays or none: the engine
of a model that drafts for itself seats what its module leaves between
steps by it. ``token_bytes`` is what a token costs in the pools and
``state_bytes`` what a sequence costs in the state arrays.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import numpy as np


class PagedKVCache:
    """Fixed-page KV pool with per-sequence block tables.

    Args:
        num_layers: number of transformer layers (one K and one V array
            per layer).
        num_pages: total pages INCLUDING the reserved scratch page 0;
            usable capacity is ``num_pages - 1`` pages.
        page_size: tokens per page.
        num_kv_heads: KV heads per token (``n_kv_head`` for GQA Llama,
            ``n_head`` for MHA GPT-2).
        head_dim: per-head feature dim. A pool's last dimension is
            ``num_kv_heads * head_dim``, head ``i`` on features
            ``[i * head_dim, (i + 1) * head_dim)``.
        dtype: cache array dtype (the model's activation dtype).
        layer_windows: per layer ``None`` (full) or the layer's window
            in tokens; empty is every layer full. One window for all.
        window_pages: a window pool's pages INCLUDING its scratch page
            0 (:meth:`window_pool_pages` sizes it for a number of
            sequences); needed where a layer has a window.
        window_burst: the most tokens one program writes for one
            sequence (the engine's prefill chunk).
        latent_row: a latent-attention model's row width as held. A
            layer then has ONE pool ``[num_pages, page_size,
            latent_row]``, from whose rows keys and values are both
            read: ``k`` holds it and ``v`` is empty. Tables, slots and
            the free list are what they are for any model of one kind.
        index_row: with ``latent_row``: each layer also has a pool of
            index keys ``[num_pages, page_size, index_row]``, which ``v``
            holds, under the latent pool's own tables and slots.
        state_shapes: one entry a layer that keeps a state and no pool
            (``num_layers`` counts the layers with pools alone): what a
            sequence keeps there, a sequence of ``(shape, dtype)``
            (``None``: the cache's), an array each
            (``Serving.state_arrays``); needs ``seats``.
        seats: sequences that can hold a seat at once; 0 keeps no seat
            map.
    """

    def __init__(self, num_layers: int, num_pages: int, page_size: int,
                 num_kv_heads: int, head_dim: int, dtype=None, *,
                 layer_windows: Sequence[Optional[int]] = (),
                 window_pages: Optional[int] = None, window_burst: int = 1,
                 latent_row: Optional[int] = None,
                 index_row: Optional[int] = None,
                 state_shapes: Sequence[Sequence[tuple]] = (),
                 seats: int = 0):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is scratch)")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        import jax.numpy as jnp

        self.num_layers = num_layers
        self.num_pages = num_pages
        self.page_size = page_size
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.dtype = dtype or jnp.float32
        # Each layer's kind of pool: 0 full, 1 window.
        windows = {w for w in layer_windows if w is not None}
        if len(windows) > 1 or (layer_windows
                                and len(layer_windows) != num_layers):
            raise ValueError(
                f"layer_windows gives each of {num_layers} layers None or "
                f"the one window they share: {tuple(layer_windows)}")
        self.window: Optional[int] = windows.pop() if windows else None
        self.layer_kinds = tuple(int(w is not None) for w in layer_windows) \
            or (0,) * num_layers
        self.window_seq_pages = self.window_burst_pages = 0
        self.num_window_pages = 0
        if self.window is not None:
            self.window_seq_pages, self.window_burst_pages = \
                self._window_seat_pages(self.window, page_size, window_burst)
            self.num_window_pages = window_pages or 0
            if self.max_window_seqs < 1:
                raise ValueError(
                    f"window_pages={self.num_window_pages} holds no "
                    f"sequence: one needs {self.window_seq_pages} pages, "
                    f"a chunk {self.window_burst_pages} more, and page 0 "
                    f"is scratch")
        if latent_row and self.window is not None:
            raise ValueError("a latent pool has no window layers")
        if index_row and not latent_row:
            raise ValueError("index keys stand beside a latent pool")
        self.latent_row = latent_row
        width = latent_row or num_kv_heads * head_dim
        # What ``v`` holds a row of: V, nothing, or a latent layer's
        # index keys.
        v_width = index_row if latent_row else width
        self.k: List = [jnp.zeros(
            (self.num_window_pages if kind else num_pages, page_size, width),
            self.dtype) for kind in self.layer_kinds]
        self.v: List = [jnp.zeros(k.shape[:2] + (v_width,), self.dtype)
                        for k in self.k] if v_width else []
        if state_shapes and seats < 1:
            raise ValueError("a layer that keeps a state needs `seats`")
        # The state arrays, layer by layer, each of its own dtype; row 0
        # is scratch.
        self.state: List = [
            jnp.zeros((seats + 1, *shape), dtype or self.dtype)
            for entry in state_shapes for shape, dtype in entry]
        # Bytes one sequence costs in them: its seat's row in each.
        self.state_bytes = sum(a[0].nbytes for a in self.state)
        self.total_seats = seats
        self._free_seats: List[int] = list(range(seats, 0, -1))
        self._seats: Dict[str, int] = {}
        # The window pools' free list and each sequence's sliding table,
        # [first logical page, its pages from there].
        self._wfree: List[int] = list(range(self.num_window_pages - 1, 0, -1))
        self._wtables: Dict[str, list] = {}
        # LIFO free list over pages 1..num_pages-1 (0 is scratch).
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._tables: Dict[str, List[int]] = {}
        # page id -> number of block tables referencing it. Pages on
        # the free list (or retained by the prefix cache) have no entry.
        self._refs: Dict[int, int] = {}
        # The tables as a program takes them, a kind (see the module's
        # docstring), grown by doubling (``_fit``). ``_span[kind][row]``
        # is the row's ``(first logical page held, one past its last)``
        # and ``_changed[kind][row]`` what ``_writes`` read when the row
        # was last written.
        self._table = [np.zeros((1, 1), np.int32) for _ in self.kinds]
        self._span = [np.zeros((1, 2), np.int64) for _ in self.kinds]
        self._changed = [np.zeros(1, np.int64) for _ in self.kinds]
        self._writes = 0
        self._rows: Dict[str, int] = {}
        self._free_rows: List[int] = []
        # Optional prefix-cache hook (see PrefixCache): retain(page)
        # keeps a ref-0 page reclaimable instead of freeing it;
        # reclaim(n) evicts up to n retained pages back to the free
        # list; reclaimable() counts pages reclaim could recover.
        self._retainer = None

    # ---- accounting -------------------------------------------------

    @staticmethod
    def _window_seat_pages(window: int, page_size: int, burst: int):
        """``(a sequence's seat, the pool's burst)`` in pages: a window of
        positions lies on ``ceil(window / page_size) + 1`` pages at most
        wherever it starts; while ``burst`` tokens are written after the
        ``window - 1`` before them, on that many more."""
        seat = -(-window // page_size) + 1
        return seat, -(-(window - 1 + max(1, burst)) // page_size) + 1 - seat

    @classmethod
    def window_pool_pages(cls, window: int, page_size: int, seqs: int,
                          burst: int = 1) -> int:
        """``window_pages`` for ``seqs`` sequences: a seat each, one
        burst, and the scratch page."""
        seat, extra = cls._window_seat_pages(window, page_size, burst)
        return seqs * seat + extra + 1

    def pages_for(self, num_tokens: int) -> int:
        """Pages needed to hold ``num_tokens`` tokens."""
        return max(0, math.ceil(num_tokens / self.page_size))

    @property
    def token_bytes(self) -> int:
        """Bytes one token costs in the pools of every layer, as held: a
        row of K and one of V a layer, or a latent layer's one row (and
        its index key, where it has an indexer)."""
        return sum(a.shape[2] * a.dtype.itemsize for a in self.k + self.v)

    def seats_in_use(self) -> int:
        return len(self._seats)

    def seat(self, seq_id: str) -> int:
        """``seq_id``'s row of the state arrays (never 0, the scratch)."""
        return self._seats[seq_id]

    @property
    def kinds(self) -> tuple:
        """The kinds of pool held: ``(0,)``, or ``(0, 1)`` with window
        layers. What a program takes a kind (``dests``, block tables)
        is given in this order."""
        return (0,) if self.window is None else (0, 1)

    @property
    def total_pages(self) -> int:
        """Usable pages of a full layer's pool (excludes scratch)."""
        return self.num_pages - 1

    @property
    def max_window_seqs(self) -> int:
        """Sequences the window pools have seats for."""
        return (self.num_window_pages - 1 - self.window_burst_pages) \
            // self.window_seq_pages

    def free_pages(self) -> int:
        """Allocatable pages: the free list plus whatever the retainer
        could evict on demand (cached-but-unreferenced prefix pages)."""
        n = len(self._free)
        if self._retainer is not None:
            n += self._retainer.reclaimable()
        return n

    def used_pages(self) -> int:
        """Pages referenced by at least one live sequence."""
        return self.total_pages - self.free_pages()

    def window_pages_owned(self) -> int:
        """Window-pool pages in sequences' sliding tables."""
        return self.num_window_pages - 1 - len(self._wfree) \
            if self.window is not None else 0

    def utilization(self) -> float:
        """Fraction of usable pages currently owned by sequences, over
        the pages of both kinds where there are two."""
        if self.window is None:
            return self.used_pages() / self.total_pages
        return (self.used_pages() + self.window_pages_owned()) \
            / (self.total_pages + self.num_window_pages - 1)

    def num_sequences(self) -> int:
        return len(self._tables)

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    # ---- allocation -------------------------------------------------

    def allocate(self, seq_id: str, num_tokens: int) -> bool:
        """Reserve pages for a new sequence of ``num_tokens`` tokens.

        All-or-nothing: returns False (allocating nothing) if the free
        list cannot cover the request. Raises if ``seq_id`` already has
        a table — callers must :meth:`free` before re-allocating.
        Where seats are kept the sequence takes one too, and the call
        fails when none is free, whatever the pages.
        """
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id!r} already allocated")
        need = self.pages_for(max(1, num_tokens))
        if not self._has_seat() or not self._reserve(need):
            return False
        self._seat(seq_id)
        self._grant(seq_id, 0, [self._take_free() for _ in range(need)])
        return True

    def allocate_shared(self, seq_id: str, num_tokens: int,
                        prefix_pages: Sequence[int]) -> bool:
        """Reserve pages for a new sequence whose first
        ``len(prefix_pages)`` pages are already-filled shared pages (a
        prefix-cache hit): those are grafted in by refcount bump, and
        only the tail is drawn from the free list. All-or-nothing —
        on failure nothing is referenced."""
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id!r} already allocated")
        if self.window is not None and prefix_pages:
            raise ValueError(
                "a window layer's pages are not shared: the prefix's are "
                "given back as its window slides on")
        need = self.pages_for(max(1, num_tokens))
        tail = need - len(prefix_pages)
        if tail < 0:
            raise ValueError(
                f"prefix of {len(prefix_pages)} pages exceeds the "
                f"{need}-page allocation of {seq_id!r}")
        # Pin the shared pages FIRST: reserving the tail may evict
        # retained pages, and a pinned (referenced) page is never on
        # the retainer's eviction list.
        for page in prefix_pages:
            self._incref(page)
        if not self._has_seat() or not self._reserve(tail):
            for page in reversed(prefix_pages):
                self._decref(page)  # rollback: back to parked/free
            return False
        self._seat(seq_id)
        self._grant(seq_id, 0, list(prefix_pages) + [
            self._take_free() for _ in range(tail)])
        return True

    def extend(self, seq_id: str, num_tokens_total: int) -> bool:
        """Grow ``seq_id``'s allocation to cover ``num_tokens_total``
        tokens. All-or-nothing; True when capacity is already enough."""
        table = self._tables.get(seq_id)
        if table is None:
            raise KeyError(f"sequence {seq_id!r} has no allocation")
        need = self.pages_for(num_tokens_total) - len(table)
        if need <= 0:
            return True
        if not self._reserve(need):
            return False
        self._grant(seq_id, 0, [self._take_free() for _ in range(need)])
        return True

    def free(self, seq_id: str) -> None:
        """Release a sequence's pages and its seat (idempotent). A page
        returns to the pool only when its last reference drops; ref-0
        pages the retainer claims stay out of the free list but
        reclaimable."""
        table = self._tables.pop(seq_id, None)
        row = self._rows.pop(seq_id, None)
        if row is not None:
            for kind in self.kinds:
                self._table[kind][row] = 0
                self._span[kind][row] = 0
                self._touch(kind, row)
            self._free_rows.append(row)
        seat = self._seats.pop(seq_id, None)
        if seat is not None:
            self._free_seats.append(seat)
        first_pages = self._wtables.pop(seq_id, None)
        if first_pages:
            self._wfree.extend(reversed(first_pages[1]))
        if not table:
            return
        # LIFO reuse keeps the hot working set in a few pages.
        for page in reversed(table):
            self._decref(page)

    # ---- a window layer's sliding tables -----------------------------

    def _has_seat(self) -> bool:
        """Whether one more sequence can be seated: in the window pools
        (always, for a model without window layers) and in the seat map
        (always, where none is kept)."""
        return (self.window is None
                or len(self._wtables) < self.max_window_seqs) \
            and (not self.total_seats or bool(self._free_seats))

    def _seat(self, seq_id: str) -> None:
        """What a new sequence holds before its first page: empty tables,
        a row of the kept ones, and a seat where seats are kept."""
        self._tables[seq_id] = []
        if self.window is not None:
            self._wtables[seq_id] = [0, []]
        if self.total_seats:
            self._seats[seq_id] = self._free_seats.pop()
        if not self._free_rows:
            self._fit(rows=len(self._table[0]) + 1)
        self._rows[seq_id] = self._free_rows.pop()

    def _fit(self, rows: int = 0, columns: int = 0) -> None:
        """Have the kept tables hold ``rows`` rows and ``columns``
        columns: twice what is asked for where they do not."""
        have, wide = self._table[0].shape
        if rows <= have and columns <= wide:
            return
        shape = (max(have, 2 * rows), max(wide, 2 * columns))

        def grown(old, shape):
            new = np.zeros(shape, old.dtype)
            new[tuple(slice(n) for n in old.shape)] = old
            return new

        self._table = [grown(a, shape) for a in self._table]
        self._span = [grown(a, (shape[0], 2)) for a in self._span]
        self._changed = [grown(a, shape[:1]) for a in self._changed]
        self._free_rows.extend(range(shape[0] - 1, have - 1, -1))

    def _touch(self, kind: int, row: int) -> None:
        self._writes += 1
        self._changed[kind][row] = self._writes

    def _grant(self, seq_id: str, kind: int, pages: List[int]) -> None:
        """``pages`` behind those ``seq_id`` holds of ``kind``: in its
        list and in its row of the kept table, the one writer of both on
        the right."""
        first, held = self._logical_pages(seq_id, kind)
        row = self._rows[seq_id]
        start = first + len(held)
        end = start + len(pages)
        self._fit(columns=end)
        held.extend(pages)
        self._table[kind][row, start:end] = pages
        self._span[kind][row] = first, end
        self._touch(kind, row)

    def slide(self, seq_id: str, lo: int, hi: int) -> int:
        """Before a program whose queries for ``seq_id`` stand at
        positions ``lo ..`` and which writes its positions up to ``hi -
        1``: have its window table hold the pages of positions ``lo -
        window + 1 .. hi - 1`` and give back every page left of them.
        ``lo == hi`` keeps what the next query, at ``lo``, will see of
        what is written. Returns the pages given back; 0, always, for a
        model without window layers. Cannot fail: a seat holds the
        pages (see the module docstring)."""
        held = self._wtables.get(seq_id)
        if held is None:
            if self.window is None:
                return 0
            raise KeyError(f"sequence {seq_id!r} has no allocation")
        first, owned = held
        keep = max(0, lo - self.window + 1) // self.page_size
        released = min(max(0, keep - first), len(owned))
        row = self._rows[seq_id]
        if released:
            self._wfree.extend(reversed(owned[:released]))
            del owned[:released]
            self._table[1][row, first:first + released] = 0
            self._touch(1, row)
        held[0] = first = first + released if owned else keep
        self._span[1][row] = first, first + len(owned)
        need = self.pages_for(hi) - first - len(owned)
        if need > 0:
            self._grant(seq_id, 1, [self._wfree.pop() for _ in range(need)])
        return released

    def slide_rows(self, seq_ids: Sequence[str], rows: np.ndarray,
                   lo: np.ndarray, hi: np.ndarray) -> int:
        """:meth:`slide` for a batch, ``seq_ids[i]`` in row ``rows[i]``
        from ``lo[i]`` to ``hi[i]``: only the sequences whose window
        crosses a page's edge are visited, which is one step in
        ``page_size`` each. Returns the pages given back in all."""
        if self.window is None:
            return 0
        span = self._span[1][rows]
        keep = np.maximum(0, lo - self.window + 1) // self.page_size
        due = (keep > span[:, 0]) | ((hi - 1) // self.page_size >= span[:, 1])
        return sum(self.slide(seq_ids[i], int(lo[i]), int(hi[i]))
                   for i in np.flatnonzero(due))

    def window_table(self, seq_id: str):
        """``(first logical page, its pages from there)`` of ``seq_id``."""
        first, pages = self._wtables[seq_id]
        return first, list(pages)

    def pages_read(self, pos, kind: int = 0):
        """Pages of one layer of ``kind`` that a query at position
        ``pos`` reads: its whole context, or its window's span. Of an
        array of positions, an array."""
        last = pos // self.page_size
        if kind == 0:
            return last + 1
        return last - np.maximum(0, pos - self.window + 1) \
            // self.page_size + 1

    # ---- refcount plumbing ------------------------------------------

    def _take_free(self) -> int:
        page = self._free.pop()
        self._refs[page] = 1
        return page

    def _incref(self, page: int) -> None:
        n = self._refs.get(page, 0)
        if n == 0 and self._retainer is not None:
            # Page was sitting in the retainer's reclaimable set; it is
            # referenced again and must not be evicted under it.
            self._retainer.activate(page)
        self._refs[page] = n + 1

    def _decref(self, page: int) -> None:
        n = self._refs.get(page, 0) - 1
        if n > 0:
            self._refs[page] = n
            return
        self._refs.pop(page, None)
        if self._retainer is not None and self._retainer.retain(page):
            return  # cached: reclaimable, but its KV stays warm
        self._free.append(page)

    def _reserve(self, need: int) -> bool:
        """Ensure ``need`` pages are on the free list, evicting retained
        prefix pages LRU if that closes the gap."""
        short = need - len(self._free)
        if short > 0 and self._retainer is not None:
            self._retainer.reclaim(short)
        return need <= len(self._free)

    # ---- addressing -------------------------------------------------

    def block_table(self, seq_id: str) -> List[int]:
        return list(self._tables[seq_id])

    def num_seq_pages(self, seq_id: str) -> int:
        """Pages currently allocated to ``seq_id`` (no copy — the
        engine reads this per step to trim block-table widths)."""
        return len(self._tables[seq_id])

    def _logical_pages(self, seq_id: str, kind: int):
        """``(first, pages)``: ``seq_id``'s pages of ``kind`` and the
        logical page the first of them is."""
        if kind:
            return self._wtables[seq_id]
        return 0, self._tables[seq_id]

    def slot(self, seq_id: str, pos: int, kind: int = 0) -> int:
        """Flat slot index (into ``[num_pages*page_size]``) of logical
        token position ``pos`` of sequence ``seq_id`` in a pool of
        ``kind``. A position a window table has slid past has none left:
        it is given a slot of the scratch page."""
        first, table = self._logical_pages(seq_id, kind)
        page = pos // self.page_size - first
        if page >= len(table):
            raise IndexError(
                f"pos {pos} beyond allocation of {seq_id!r} "
                f"({len(table)} pages x {self.page_size})")
        if page < 0:
            return pos % self.page_size
        return table[page] * self.page_size + pos % self.page_size

    def rows(self, seq_ids: Sequence[str],
             batch: Optional[int] = None) -> np.ndarray:
        """Each sequence's row of the kept tables, ``[batch]``; rows past
        ``len(seq_ids)`` name row 0, which is scratch all through."""
        out = np.zeros(batch if batch is not None else len(seq_ids), np.intp)
        out[:len(seq_ids)] = [self._rows[sid] for sid in seq_ids]
        return out

    def seats(self, seq_ids: Sequence[str], batch: int) -> np.ndarray:
        """Each sequence's seat, int32 ``[batch]``; rows past
        ``len(seq_ids)`` name seat 0, the scratch row."""
        out = np.zeros(batch, np.int32)
        out[:len(seq_ids)] = [self._seats[sid] for sid in seq_ids]
        return out

    def table_version(self, rows: np.ndarray, kind: int = 0) -> int:
        """A number that moves whenever the content of one of ``rows``
        of ``kind``'s kept table does: a reader that was given these
        rows' tables when it read ``n`` holds what :meth:`table_array`
        would give it now for as long as it reads ``n``."""
        return int(self._changed[kind][rows].max(initial=0))

    def table_width(self, rows: np.ndarray) -> int:
        """The most pages one of ``rows`` holds in a full layer's pool:
        the columns a table of them needs."""
        return int(self._span[0][rows, 1].max(initial=0))

    def slots(self, rows: np.ndarray, positions: np.ndarray,
              kind: int = 0) -> np.ndarray:
        """:meth:`slot` for a batch: the flat slot in a pool of ``kind``
        of ``positions[i]`` (``[n]``, or ``[n, T]``: ``T`` positions a
        sequence) of the sequence in row ``rows[i]``, int32. A position a
        window table has slid past lies in a scratch column of the kept
        table, so it is given a slot of the scratch page, and so is a
        padding row's (row 0)."""
        page = positions // self.page_size
        index = rows if positions.ndim == 1 else rows[:, None]
        if (page >= self._span[kind][index, 1])[rows > 0].any():
            raise IndexError(
                f"a position of {positions.tolist()} lies beyond its "
                f"sequence's allocation")
        return (self._table[kind][index, page] * self.page_size
                + positions % self.page_size).astype(np.int32, copy=False)

    def table_array(self, seq_ids: Sequence[str], max_pages: int,
                    batch: Optional[int] = None, kind: int = 0
                    ) -> np.ndarray:
        """Stacked block tables ``[batch, max_pages]`` int32, padded
        with 0 (scratch) — rows past ``len(seq_ids)`` are dummy rows.
        Column ``c`` is logical page ``c`` for either ``kind``: a window
        table's columns left of its first page are scratch too. A gather
        of the sequences' rows of the kept table."""
        self._fit(columns=max_pages)
        return self._table[kind][self.rows(seq_ids, batch), :max_pages]

    def prefill_dests(self, seq_id: str, length: int,
                      bucket: int) -> np.ndarray:
        """Flat destination slots ``[bucket]`` int32 for writing a
        prefill of ``length`` real tokens padded to ``bucket``. Padding
        slots cycle through page 0 so bucketed garbage stays in scratch."""
        return self.chunk_dests(seq_id, 0, length, bucket)

    def chunk_dests(self, seq_id: str, start: int, take: int,
                    bucket: int, kind: int = 0) -> np.ndarray:
        """Flat destination slots ``[bucket]`` int32 for writing a
        prefill CHUNK covering logical positions ``[start, start+take)``
        padded to ``bucket``, in a pool of ``kind``; padding cycles
        through page 0."""
        out = np.arange(bucket, dtype=np.int32) % self.page_size  # page 0
        take = min(take, bucket)
        if take > 0:
            first, table = self._logical_pages(seq_id, kind)
            pos = np.arange(start, start + take)
            page = pos // self.page_size - first
            if page[-1] >= len(table):
                raise IndexError(
                    f"pos {start + take - 1} beyond allocation of "
                    f"{seq_id!r} ({len(table)} pages x {self.page_size})")
            held = page >= 0  # a window table has slid past the others
            out[:take][held] = (
                np.asarray(table, dtype=np.int64)[page[held]]
                * self.page_size + pos[held] % self.page_size)
        return out
