"""The cache of a decoder whose layers keep different kinds of state: pools
kept on the host as bookkeeping and on the device as arrays
(``models/decoder.py::zero_cache``, ``models/hybrid_decoder.py::zero_cache``).

- The **ring pool** serves the sliding layers.  A resident sequence owns
  one slot: a fixed run of ``ring_pages`` pages in every sliding layer,
  which its positions walk circularly (position ``j`` lives in the slot's
  page ``(j // page_size) % ring_pages``).  A slot holds the window, the
  longest prefill chunk and one page of slack, whatever the context's
  length.
- The **page pool** serves the full layers.  A sequence takes pages as its
  context needs them; a page is ``page_size`` positions in every full layer
  (the same page number in each layer's slab).

- The **state pool** serves linear-attention layers.  A resident sequence
  owns the same slot there: one fixed-size float32 recurrent state in every
  such layer, whatever the context's length.  A block-sparse layer's pooled
  keys (one row every ``kernel_stride`` positions) live page for page beside
  its pages and need no bookkeeping of their own.
- **Latent pages** serve multi-head latent attention
  (``models/latent_decoder.py::zero_cache``): a page holds its positions'
  ``[c | rotated kr]`` latents, ``[kv_lora + rope, page_size]`` a layer,
  in place of every head's keys and values.  They are the page pool's
  pages, leased and kept under sessions as the others are; a slot is then
  a decode row and holds nothing of its own.

Held as one kind of cache, every layer would pay the full layers' price.
A request is admitted only when a slot is free and the page pool can hold
its whole context (prompt and every token it will generate), so a running
sequence never waits for memory; both are returned at delivery.

A request may name a **session**: its lease is then kept at delivery
(``keep``) and the session's next turn is admitted by adding only the pages
its new positions need (``extend``); ``close_session`` returns the lease.
No sharing between sessions, no snapshots, no eviction.

Thread-safety: the engine calls everything here under its own lock.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


@dataclasses.dataclass(frozen=True)
class CacheGeometry:
    page_size: int
    window: int             # the sliding layers' window, in positions
    slots: int              # sequences resident at once (ring slots)
    ring_pages: int         # pages of one slot
    pool_pages: int         # pages of the page pool (one layer's slab)
    max_context: int        # longest prompt + generated a request may ask
    max_chunk: int          # longest prefill chunk a step carries

    @classmethod
    def make(cls, page_size: int, window: int, slots: int, pool_pages: int,
             max_context: int, max_chunk: int) -> 'CacheGeometry':
        # the window before a chunk's first token, the chunk, and the page
        # the window's first key lies in
        ring = ceil_div(window + max_chunk + page_size, page_size)
        return cls(page_size=page_size, window=window, slots=slots,
                   ring_pages=ring, pool_pages=pool_pages,
                   max_context=max_context, max_chunk=max_chunk)

    @property
    def ring_layer_pages(self) -> int:
        """Pages one sliding layer's slab holds: the slots, and one page
        that takes the padding rows' writes."""
        return self.slots * self.ring_pages + 1

    @property
    def pool_layer_pages(self) -> int:
        return self.pool_pages + 1

    @property
    def pages_per_seq(self) -> int:
        return ceil_div(self.max_context, self.page_size)

    def window_table_pages(self, q_len: int) -> int:
        """Columns of a rebased window page table for ``q_len`` queries."""
        return ceil_div(self.window - 1 + q_len + self.page_size - 1,
                        self.page_size) + 1


@dataclasses.dataclass
class Lease:
    """What one resident sequence holds of the pools."""
    slot: int               # its ring slot and its state slot alike
    pages: np.ndarray       # int32, the paged layers' pages in order


class CacheManager:
    """Free lists of the two pools.  Not thread-safe by itself."""

    def __init__(self, geometry: CacheGeometry):
        self.geometry = geometry
        self._free_slots: List[int] = list(range(geometry.slots))[::-1]
        self._free_pages: List[int] = list(range(geometry.pool_pages))[::-1]
        self.held_total = 0     # admissions that found no room
        self._kept: dict = {}   # session id -> its resident lease

    # ------------------------------------------------------------ asking
    def pages_for(self, tokens: int) -> int:
        return ceil_div(tokens, self.geometry.page_size)

    def fits_ever(self, tokens: int) -> bool:
        """Whether a request of ``tokens`` positions could be admitted into
        an empty cache."""
        g = self.geometry
        return tokens <= g.max_context and \
            self.pages_for(tokens) <= g.pool_pages

    def admit(self, tokens: int) -> Optional[Lease]:
        """A slot and the pages of ``tokens`` positions, or None (counted)
        where either pool lacks them."""
        need = self.pages_for(tokens)
        if not self._free_slots or need > len(self._free_pages):
            self.held_total += 1
            return None
        slot = self._free_slots.pop()
        pages = np.asarray([self._free_pages.pop() for _ in range(need)],
                           np.int32)
        return Lease(slot=slot, pages=pages)

    def free(self, lease: Lease) -> None:
        self._free_slots.append(lease.slot)
        self._free_pages.extend(int(p) for p in lease.pages[::-1])
        lease.pages = np.zeros((0,), np.int32)

    # ---------------------------------------------------------- sessions
    def extend(self, lease: Lease, tokens: int) -> bool:
        """Grows a kept lease to hold ``tokens`` positions in all; False
        (counted) where the page pool lacks the pages it would add."""
        need = self.pages_for(tokens) - int(lease.pages.shape[0])
        if need > len(self._free_pages):
            self.held_total += 1
            return False
        if need > 0:
            more = [self._free_pages.pop() for _ in range(need)]
            lease.pages = np.concatenate(
                [lease.pages, np.asarray(more, np.int32)])
        return True

    def keep(self, session, lease: Lease) -> None:
        """``lease`` outlives its request under ``session``."""
        self._kept[session] = lease

    def kept(self, session) -> Optional[Lease]:
        return self._kept.get(session)

    def close_session(self, session) -> bool:
        """Returns a session's lease to the pools; False if it holds none."""
        lease = self._kept.pop(session, None)
        if lease is None:
            return False
        self.free(lease)
        return True

    def close_all_sessions(self) -> None:
        for session in list(self._kept):
            self.close_session(session)

    # ----------------------------------------------------------- gauges
    @property
    def slots_in_use(self) -> int:
        return self.geometry.slots - len(self._free_slots)

    @property
    def pages_in_use(self) -> int:
        return self.geometry.pool_pages - len(self._free_pages)

    @property
    def sessions_kept(self) -> int:
        return len(self._kept)

    def fill(self) -> Tuple[float, float]:
        """(slots: ring and state pool alike, page pool) shares in use."""
        g = self.geometry
        return (self.slots_in_use / g.slots,
                self.pages_in_use / max(g.pool_pages, 1))


# ---------------------------------------------------- where positions live
def full_rows(g: CacheGeometry, lease: Lease, positions):
    """Flat rows (page x page_size + offset) of ``positions`` (an array or
    one position) in a full layer's slab."""
    return lease.pages[positions // g.page_size] * g.page_size \
        + positions % g.page_size


def completed_strides(g: CacheGeometry, lease: Lease, first: int, count: int,
                      stride: int) -> Tuple[np.ndarray, np.ndarray]:
    """The strides of ``stride`` positions whose last position lies in
    ``first .. first + count - 1``: (flat row in a layer's page slab where
    each starts, flat row in its pooled slab where its mean goes).  A page
    holds whole strides, so both follow from the stride's page."""
    ends = np.arange((first + stride) // stride * stride - 1, first + count,
                     stride)
    starts = ends - (stride - 1)
    page = lease.pages[starts // g.page_size]
    per_page = g.page_size // stride
    return (page * g.page_size + starts % g.page_size,
            page * per_page + (starts % g.page_size) // stride)


def ring_rows(g: CacheGeometry, slot: int, positions):
    """Flat rows of ``positions`` in a sliding layer's slab."""
    page = slot * g.ring_pages + (positions // g.page_size) % g.ring_pages
    return page * g.page_size + positions % g.page_size


def window_view(g: CacheGeometry, slot: int, first: int, q_len: int,
                width: int) -> Tuple[int, np.ndarray]:
    """``q_len`` queries at positions ``first ..`` of the sequence in
    ``slot``, as the attention call sees them in a sliding layer: rebased
    to the page that holds the first key inside the window.  Returns the
    rebased key length and the page table's row (``width`` columns).  Causal
    and window masks depend on differences of positions only, so the
    rebased sequence attends exactly as the whole one would; the pages
    before the window are not in the table and are never read."""
    start_page = max(0, first - (g.window - 1)) // g.page_size
    kv_len = first + q_len - start_page * g.page_size
    pages = slot * g.ring_pages \
        + (start_page + np.arange(width)) % g.ring_pages
    return kv_len, pages.astype(np.int32)
