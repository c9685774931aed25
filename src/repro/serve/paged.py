"""Paged slot pool: block tables over a shared page arena.

The dense slot pool reserves a full ``(capacity, max_len)`` cache row per
slot.  This module re-lays every cache group a family DECLARES pageable
(``models.paged_groups`` — part of the slot-state protocol) as shared
page arenas plus per-slot block tables:

    seq   dense {"k": (L, B, S, KV, hd), "v": ...}
          paged {"k": (L, n_pages, page, KV, hd), "v": ...,
                 "bt": (L, B, nblk) int32}          nblk = S // page
    slot  dense {"conv": (L, B, K-1, d), ...dense carries}
          paged {"conv": (L, n_pages, K-1, d), ...dense carries,
                 "bt": (L, B, 1) int32}             the whole tail is
                                                    one page

with ``page`` the ``pad_cache_len`` quantum for ``S`` (8 below 256, 64
above).  The block table rides inside the group dict, tiled identically
per layer, so it flows through ``lax.scan`` over the layer axis with
zero plumbing changes; model code detects a paged group purely by
``"bt" in cache``.  (The transformer's slot decode scans only the table
and carries its k/v arenas whole, offsetting each layer's table into
them: ``models.transformer._run_group``.)  Leaves of a declared group
that are NOT named (xlstm's mLSTM C/n/m carries) stay dense-per-slot
inside the same dict.

Page-id conventions
-------------------
* Page ids live in ``[0, n_pages)``; the value ``n_pages`` is the OOB
  SENTINEL.  Scatters through a sentinel entry are dropped (jnp
  out-of-bounds scatter semantics) and gathers clamp it to the last page
  — the garbage read is finite and always hidden behind a ``kv_len`` /
  ring-validity / band mask, which pins masked logits to ``NEG_INF`` so
  the softmax contribution underflows to exactly 0.0.
* ONE page-id space spans every group of a pool — and, for a
  speculative pair, both the target and draft pools: page ``p`` is row
  ``p`` of EVERY group's arena in every engine sharing the allocator.  A
  request allocates ``pages_needed`` ids once and each group consumes
  the leading ``nblk_g`` of them, so draft and target memory trade
  freely inside one ``--pages`` budget instead of a static split.
* All layers of a group share one logical page-id space: page ``p`` is
  row ``p`` of every layer's arena, and ``bt`` is the same (B, nblk_g)
  table broadcast over L.

The host-side :class:`PageAllocator` owns the free list, per-namespace
refcounts (one namespace per engine sharing the arena), and the prefix
registry (rolling blake2b chain hashes of full prompt pages).
"Copy-on-write" prefix sharing needs no actual copy for full layouts:
shared pages cover only FULL pages strictly before a prompt's last
token, and every write a slot performs lands in its private tail pages.
Ring layouts can NOT alias (the donor wraps and overwrites its own
registered pages) — they register registry-only absolute-position
copies at admission and a hit RECONSTRUCTS the new slot's ring from the
resident tail pages (see ``serve/engine.py``).
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class GroupMeta:
    """Static paging geometry of one declared cache group (hashable)."""
    path: tuple      # key path to the group dict from the pool root
    kind: str        # "seq" (paged sequence axis) | "slot" (whole tail)
    leaves: tuple    # arena leaf names inside the group dict
    page: int        # positions per page ("slot": the tail length)
    nblk: int        # block-table entries per slot ("slot": 1)


@dataclasses.dataclass(frozen=True)
class PoolMeta:
    """Static paging geometry of one pool (hashable: jit-cache key).

    ``page``/``nblk`` summarize the pool for the engine: ``page`` is the
    shared sequence-group quantum (0 for pools with no seq group — the
    prefix cache then has nothing to share), ``nblk`` the per-request
    allocation bound (max over groups).  ``groups`` carries the
    per-group layout; an empty tuple is the legacy single-{"k","v"}
    geometry (kept constructible for allocator-only uses in tests).
    """
    page: int
    nblk: int
    n_pages: int     # arena depth; also the OOB sentinel page id
    groups: tuple = ()

    @property
    def sentinel(self) -> int:
        return self.n_pages


def page_quantum(padded_len: int) -> int:
    """The natural page size for a padded cache axis — the same quantum
    ``pad_cache_len`` rounded to, re-derived from its output (both
    branches of the quantum divide their padded lengths exactly)."""
    return 8 if padded_len <= 256 else 64


def pool_meta(cfg, cache_shapes: Any, pages: Optional[int] = None
              ) -> Optional[PoolMeta]:
    """Paging geometry for a pool (concrete or ``jax.eval_shape`` tree).

    Reads the family's ``paged_groups`` declaration.  Returns None when
    the family declares nothing pageable, or its seq groups disagree on
    the padded sequence length (prefix pages must mean the same token
    span in every arena — never violated in the current zoo).
    """
    from repro import models

    decl = models.paged_groups(cfg)
    groups = []
    seq_geom = set()
    B = None
    for key in sorted(decl):
        kind, leaves = decl[key]
        if key not in cache_shapes:
            continue
        g = cache_shapes[key]
        lead = g[leaves[0]]
        B = lead.shape[1]
        if kind == "seq":
            S = lead.shape[2]
            page = page_quantum(S)
            if S % page:
                return None
            seq_geom.add((page, S // page))
            groups.append(GroupMeta(path=(key,), kind="seq",
                                    leaves=tuple(leaves), page=page,
                                    nblk=S // page))
        else:
            groups.append(GroupMeta(path=(key,), kind="slot",
                                    leaves=tuple(leaves),
                                    page=int(lead.shape[2]), nblk=1))
    if not groups or len(seq_geom) > 1:
        return None
    page, _ = seq_geom.pop() if seq_geom else (0, 0)
    nblk = max(g.nblk for g in groups)
    return PoolMeta(page=page, nblk=nblk,
                    n_pages=int(pages) if pages else B * nblk,
                    groups=tuple(groups))


def pool_fallback_reason(cfg) -> Optional[str]:
    """Why a config cannot serve paged — or None when it can.  The named
    counterpart of the old silent ``pool_kind`` flip."""
    from repro import models

    if not models.paged_groups(cfg):
        return (f"{cfg.family} declares no pageable cache groups "
                "(O(1) recurrent state only)")
    return None


def build_paged_pool(fam, cfg, capacity: int, max_len: int,
                     pages: Optional[int] = None,
                     n_pages: Optional[int] = None):
    """Construct a zeroed paged pool for ``fam``/``cfg``.

    Returns ``(pool, meta)``; ``meta is None`` means the family declares
    nothing pageable and ``pool`` is the ordinary dense pool.
    ``n_pages`` overrides the arena depth directly (a speculative pair
    shares one page-id space, so both pools must be built to the SAME
    depth regardless of their own defaults).
    """
    shapes = jax.eval_shape(
        lambda: fam.init_cache(cfg, capacity, max_len))
    meta = pool_meta(cfg, shapes, pages)
    if meta is None:
        return fam.init_cache(cfg, capacity, max_len), None
    if n_pages is not None and n_pages != meta.n_pages:
        meta = dataclasses.replace(meta, n_pages=int(n_pages))

    paged_paths = {g.path[0]: g for g in meta.groups}

    def dense(node):
        if isinstance(node, dict):
            return {k: dense(v) for k, v in node.items()}
        return jnp.zeros(node.shape, node.dtype)

    out = {}
    for key, grp in shapes.items():
        g = paged_paths.get(key)
        if g is None:
            out[key] = dense(grp)
            continue
        og = {}
        L = grp[g.leaves[0]].shape[0]
        for lk, leaf in grp.items():
            if lk in g.leaves:
                # (L, B, S, ...) -> (L, n_pages, page, ...) for seq;
                # (L, B, tail...) -> (L, n_pages, tail...) for slot
                tail = leaf.shape[3:] if g.kind == "seq" else leaf.shape[2:]
                og[lk] = jnp.zeros((L, meta.n_pages, g.page) + tail
                                   if g.kind == "seq" else
                                   (L, meta.n_pages) + leaf.shape[2:],
                                   leaf.dtype)
            else:
                og[lk] = jnp.zeros(leaf.shape, leaf.dtype)
        og["bt"] = jnp.full((L, capacity, g.nblk), meta.sentinel,
                            jnp.int32)
        out[key] = og
    return out, meta


def pages_needed(prompt_len: int, max_new: int, meta: PoolMeta) -> int:
    """Pages a request needs up-front so no mid-flight top-up is ever
    required — the max over the pool's groups, since every group
    consumes the leading ``nblk_g`` ids of one shared allocation.  For a
    seq group the ``nblk`` clamp covers both layouts at once: a full
    cache fits ``prompt + max_new`` inside ``nblk`` pages by the
    engine's admission check, and a ring layout wraps at ``nblk *
    page``; a slot group always needs exactly its single block."""
    if not meta.groups:  # legacy single-seq-group geometry
        return min(-(-(prompt_len + max_new) // meta.page), meta.nblk)
    need = 0
    for g in meta.groups:
        if g.kind == "seq":
            need = max(need,
                       min(-(-(prompt_len + max_new) // g.page), g.nblk))
        else:
            need = max(need, 1)
    return need


# --------------------------------------------------------------- jit helpers
def _paged_map(meta: PoolMeta):
    return {g.path[0]: g for g in meta.groups}


def admit_scatter(pool, rows, slots, bt_rows, meta: PoolMeta):
    """Scatter freshly-prefilled dense cache rows into a paged pool.
    jit-safe; donated in the engine's admit step.

    pool: the live pool pytree (paged groups carry "bt").
    rows: matching DENSE pytree of (L, npad, S, ...) prefill scratch rows
          (no "bt" keys — prefill always runs on dense scratch).
    slots: (npad,) int32 slot ids; padding rows carry the OOB slot id.
    bt_rows: (npad, meta.nblk) int32 page ids per admitted row; each
          group consumes its leading ``nblk_g`` columns; unallocated
          blocks and padding rows carry the page sentinel.
    """
    paged = _paged_map(meta)

    def dense_scatter(p, r):
        return jax.tree.map(
            lambda pl, rl: pl.at[:, slots].set(rl.astype(pl.dtype),
                                               mode="drop"), p, r)

    out = {}
    npad = bt_rows.shape[0]
    for key, grp in pool.items():
        g = paged.get(key)
        if g is None:
            out[key] = dense_scatter(grp, rows[key])
            continue
        bt_g = bt_rows[:, :g.nblk]
        flat = bt_g.reshape(-1)  # (npad * nblk_g,)
        og = {}
        L = grp["bt"].shape[0]
        for lk, leaf in grp.items():
            if lk == "bt":
                og[lk] = leaf.at[:, slots].set(
                    jnp.broadcast_to(bt_g[None], (L, npad, g.nblk)),
                    mode="drop")
            elif lk in g.leaves:
                chunks = rows[key][lk].reshape(
                    (L, npad * g.nblk) + leaf.shape[2:])
                og[lk] = leaf.at[:, flat].set(chunks.astype(leaf.dtype),
                                              mode="drop")
            else:
                og[lk] = leaf.at[:, slots].set(
                    rows[key][lk].astype(leaf.dtype), mode="drop")
        out[key] = og
    return out


def register_copy(pool, reg_pids, reg_blk, rows, meta: PoolMeta):
    """Copy prefill-scratch pages into REGISTRY-ONLY pages — the ring
    prefix-cache path: ring block tables wrap, so future hits reconstruct
    from these absolute-position copies instead of aliasing live ring
    pages (which the donor keeps overwriting).

    rows: the (L, npad, S, ...) prefill scratch handed to
    ``admit_scatter`` (ring layout for windowed configs — the caller
    passes the RING block index of each wanted absolute page in
    ``reg_blk``); reg_pids/reg_blk: (npad, nreg) int32 — destination
    page id and source block index per copy; sentinel page ids drop.
    Only seq groups participate (slot tails cannot be shared).
    """
    paged = _paged_map(meta)
    flat_pid = reg_pids.reshape(-1)
    out = {}
    for key, grp in pool.items():
        g = paged.get(key)
        if g is None or g.kind != "seq":
            out[key] = grp
            continue
        og = dict(grp)
        npad, nreg = reg_pids.shape
        for lk in g.leaves:
            leaf = grp[lk]
            L = leaf.shape[0]
            r = rows[key][lk]  # (L, npad, S, ...)
            rp = r.reshape((L, npad, r.shape[2] // g.page, g.page)
                           + r.shape[3:])
            blk = jnp.minimum(reg_blk, rp.shape[2] - 1)
            src = jnp.take_along_axis(
                rp, blk.reshape((1, npad, nreg)
                                + (1,) * (rp.ndim - 3)), axis=2)
            src = src.reshape((L, npad * nreg, g.page) + r.shape[3:])
            og[lk] = leaf.at[:, flat_pid].set(src.astype(leaf.dtype),
                                              mode="drop")
        out[key] = og
    return out


def ring_restore_copy(pool, src_pids, dst_pids, meta: PoolMeta):
    """Arena-to-arena page copy for ring prefix-hit reconstruction.

    src_pids/dst_pids: (npad, nblk) int32 — for each admitted row, copy
    registry page ``src_pids[i, j]`` into the row's private ring page
    ``dst_pids[i, j]``; sentinel destinations drop, sentinel sources
    clamp (their destinations are sentinel too).  Applies to every seq
    group (all share the page-id space and geometry).
    """
    paged = _paged_map(meta)
    flat_src = src_pids.reshape(-1)
    flat_dst = dst_pids.reshape(-1)
    out = {}
    for key, grp in pool.items():
        g = paged.get(key)
        if g is None or g.kind != "seq":
            out[key] = grp
            continue
        og = dict(grp)
        for lk in g.leaves:
            leaf = grp[lk]
            n_pages = leaf.shape[1]
            src = leaf[:, jnp.minimum(flat_src, n_pages - 1)]
            og[lk] = leaf.at[:, flat_dst].set(src, mode="drop")
        out[key] = og
    return out


def evict_clear(pool, slots, zero_pids, meta: PoolMeta):
    """Clear evicted slots.  Dense leaves zero their rows; paged groups
    zero the handed-back pages listed in ``zero_pids`` (padded with the
    page sentinel — prefix-registered pages are retained, so they are
    simply absent from the list) and reset the rows' block tables to the
    sentinel."""
    paged = _paged_map(meta)

    def dense_clear(p):
        return jax.tree.map(
            lambda pl: pl.at[:, slots].set(0, mode="drop"), p)

    out = {}
    for key, grp in pool.items():
        g = paged.get(key)
        if g is None:
            out[key] = dense_clear(grp)
            continue
        og = {}
        L, _, nblk = grp["bt"].shape
        for lk, leaf in grp.items():
            if lk == "bt":
                og[lk] = leaf.at[:, slots].set(
                    jnp.full((L, slots.shape[0], nblk), meta.sentinel,
                             jnp.int32), mode="drop")
            elif lk in g.leaves:
                og[lk] = leaf.at[:, zero_pids].set(0, mode="drop")
            else:
                og[lk] = leaf.at[:, slots].set(0, mode="drop")
        out[key] = og
    return out


def set_block_tables(pool, slots, bt_rows, meta: PoolMeta):
    """Point admitted rows' block tables at pages WITHOUT touching arena
    bytes — the prefix-hit admission path (leading entries alias resident
    pages; tail pages fill via the decode-scan tail prefill)."""
    paged = _paged_map(meta)
    out = {}
    npad = bt_rows.shape[0]
    for key, grp in pool.items():
        g = paged.get(key)
        if g is None:
            out[key] = grp
            continue
        L = grp["bt"].shape[0]
        bt_g = bt_rows[:, :g.nblk]
        out[key] = {**grp, "bt": grp["bt"].at[:, slots].set(
            jnp.broadcast_to(bt_g[None], (L, npad, g.nblk)),
            mode="drop")}
    return out


# ------------------------------------------------------------ prefix hashing
def prefix_digests(tokens, page: int) -> list:
    """Rolling chain digests of each FULL page of a prompt.

    ``digest[j]`` commits to tokens ``[0, (j+1) * page)`` — chaining means
    a page is only ever shared under an identical full prefix, never by
    content coincidence at different offsets.
    """
    toks = np.asarray(tokens, np.int64)
    out = []
    h = b""
    for j in range(len(toks) // page):
        h = hashlib.blake2b(
            h + toks[j * page:(j + 1) * page].tobytes(),
            digest_size=16).digest()
        out.append(h)
    return out


# ------------------------------------------------------------ host allocator
class PageAllocator:
    """Host-side page bookkeeping for one page-id space: free list,
    per-namespace refcounts, and the prefix registry with LRU retention
    of zero-ref registered pages (their bytes ARE the cached value —
    they are reclaimed lazily, oldest first, only when the free list
    runs dry).

    ``namespaces`` > 1 merges several engines' arenas into ONE id space
    (the speculative draft/target pair): page ``p`` is a row in every
    engine's arenas, each engine holds references in its own namespace,
    and the page returns to the free list only when EVERY namespace has
    released it — so pages freed by one engine's retirements are
    immediately allocatable by the other, with no static budget split.
    The prefix registry lives in namespace 0 (the target engine).
    """

    def __init__(self, meta: PoolMeta, namespaces: int = 1):
        self.meta = meta
        self.namespaces = namespaces
        self.free: list[int] = list(range(meta.n_pages))[::-1]
        self.refcount = np.zeros((meta.n_pages, namespaces), np.int32)
        self.registry: dict[bytes, int] = {}       # digest -> page id
        self.page_key: dict[int, bytes] = {}       # page id -> digest
        self.lru: OrderedDict[int, None] = OrderedDict()
        self.highwater = 0

    # -- capacity -----------------------------------------------------------
    def pages_in_use(self) -> int:
        return self.meta.n_pages - len(self.free) - len(self.lru)

    def available(self) -> int:
        return len(self.free) + len(self.lru)

    # -- alloc / release ----------------------------------------------------
    def alloc(self, n: int, ns=(0,)) -> Optional[list]:
        """Take ``n`` pages (refcount 1 in each namespace of ``ns``),
        reclaiming retained prefix pages oldest-first if the free list
        runs dry.  Returns None — allocating NOTHING — when fewer than
        ``n`` are available: admission backpressure is all-or-nothing
        per request."""
        if n > self.available():
            return None
        out = []
        for _ in range(n):
            if self.free:
                pid = self.free.pop()
            else:
                pid, _ = self.lru.popitem(last=False)
                self._unregister(pid)
            for i in ns:
                self.refcount[pid, i] = 1
            out.append(pid)
        self.highwater = max(self.highwater, self.pages_in_use())
        return out

    def incref(self, pids, ns: int = 0) -> None:
        for pid in pids:
            if self.refcount[pid].sum() == 0:
                # a retained registry page comes back to life
                self.lru.pop(pid, None)
            self.refcount[pid, ns] += 1
        self.highwater = max(self.highwater, self.pages_in_use())

    def release(self, pids, ns: int = 0) -> list:
        """Drop one reference per page in namespace ``ns``; returns the
        page ids whose bytes must be ZEROED (every namespace's refcount
        hit zero and the page is not prefix-registered — registered
        pages are retained in the LRU with their bytes intact)."""
        zero = []
        for pid in pids:
            self.refcount[pid, ns] -= 1
            if self.refcount[pid].sum() > 0:
                continue
            if pid in self.page_key:
                self.lru[pid] = None
                self.lru.move_to_end(pid)
            else:
                self.free.append(pid)
                zero.append(pid)
        return zero

    # -- prefix registry ----------------------------------------------------
    def _unregister(self, pid: int) -> None:
        d = self.page_key.pop(pid, None)
        if d is not None:
            self.registry.pop(d, None)

    def register(self, digests, pids) -> None:
        """Record ``pids[j]`` as holding the page whose chain digest is
        ``digests[j]``.  First writer wins — re-registering a digest that
        already resolves elsewhere is a no-op (the resident page keeps
        serving hits)."""
        for d, pid in zip(digests, pids):
            if d in self.registry or pid in self.page_key:
                continue
            self.registry[d] = pid
            self.page_key[pid] = d

    def flush_registry(self) -> list:
        """Drop the entire prefix registry — the arena-fault degradation
        path: once a poisoned slot may have flowed NaNs through shared
        pages, no resident prefix can be trusted for reuse.

        Zero-ref retained pages return to the free list; their ids are
        returned so the engine can zero their bytes in the next eviction
        scatter.  Pages still referenced by live slots are merely
        unregistered: their current holders keep decoding, and when the
        last reference drops, ``release`` now zeroes and frees them like
        any private page.
        """
        zero = list(self.lru.keys())
        for pid in zero:
            self.free.append(pid)
        self.lru.clear()
        self.registry.clear()
        self.page_key.clear()
        return zero

    def lookup(self, digests) -> Optional[list]:
        """Resolve a chain of share digests to resident pages.  Partial
        chains are misses: every looked-up position's bytes must be
        resident (full-KV shares look up the whole prefix; ring shares
        look up only the tail pages that can feed the ring — the chained
        digest of the last page already commits to the entire prefix)."""
        out = []
        for d in digests:
            pid = self.registry.get(d)
            if pid is None:
                return None
            out.append(pid)
        return out
