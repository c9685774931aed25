"""Pallas TPU kernels: flash-decode against a KV cache (slot-serving family).

Decode attention is memory-bound: the whole KV cache streams through VMEM
once per step, with the cache axis innermost in the grid and a running
(m, l, acc) per (batch, kv-head) in VMEM scratch — all G query heads of a
kv group are processed together as a (G, hd) tile so the cache block is
read exactly once per group (the GQA bandwidth win).  The head-major
kernel grids (B, KV, Sk/BK); the pool-layout kernels grid (B, Sk/BK) over
blocks that hold every kv head (see the section below).

``kv_len`` masks the unwritten cache tail (padded caches); it may be a
scalar (uniform batch) or a (B,) vector — the continuous-batching case
where every batch row is a cache slot at its own sequence length.  Rows
with kv_len == 0 (idle slots) return zeros.

Four kernels share the streaming-softmax machinery:

  * ``decode_attention``      — (B, KV, S, hd) caches, scalar/(B,) kv_len
                                (the original head-major layout);
  * ``slot_decode_attention`` — the same math over the serve engine's
                                POOL layout (B, S, KV, hd): no transpose
                                of the cache on the hot path;
  * ``ring_decode_attention`` — ring-buffer window caches: the band mask
                                is reconstructed per block from the ring
                                invariant at each row's own length;
  * ``chunk_verify_attention``— speculative verify: D+1 chunk queries per
                                row against [cache ‖ chunk] at per-row
                                offsets, cache read-only.

each with a paged twin that reads a shared page arena through block
tables.  The slot-path kernels encode done/idle rows as a negative per-row
scalar (kv_len == 0, slot_positions == -1, offsets == -1): every KV block
is skipped and the empty accumulator finalizes to exact zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bk, scale):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[pl.program_id(0)]

    @pl.when(ki * bk < kv_len)
    def _body():
        q = q_ref[0, 0]  # (G, hd)
        k = k_ref[0, 0]  # (BK, hd)
        v = v_ref[0, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        kpos = ki * bk + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _fin():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _pick_bk(S: int, cap: int = 256) -> int:
    """Largest divisor of the cache length that is <= ``cap``.

    The grid tiles the cache axis in ``bk``-sized blocks, so ``bk`` must
    divide S exactly; short caches (e.g. a serve pool with max_len=48)
    simply use one block instead of failing the old ``S % 256 == 0``
    assert and falling back to the reference path.  Cache lengths whose
    only divisors in range are tiny (e.g. prime S > 256) would silently
    degenerate into a pathological one-element-block grid — fail loudly
    instead and let the caller pad the cache or pass ``bk``.
    """
    bk = min(S, cap)
    while S % bk:
        bk -= 1
    if S > cap and bk < 32:
        raise ValueError(
            f"cache length {S} has no block divisor in [32, {cap}]; pad "
            f"the cache axis or pass bk explicitly")
    return bk


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention(q, k, v, kv_len, *, bk=None, interpret=False):
    """q: (B, H, hd); k, v: (B, KV, S, hd); kv_len: scalar or (B,) vector
    of valid lengths -> (B, H, hd).

    ``bk=None`` auto-picks the largest cache-axis block <= 256 that divides
    S.  Rows with ``kv_len == 0`` (idle/finished slots — the
    continuous-batching macro-step's ``done`` rows, folded into kv_len by
    ``ops.decode_attention``) skip every KV block and return exact zeros.
    """
    B, H, hd = q.shape
    KV, S = k.shape[1], k.shape[2]
    g = H // KV
    if bk is None:
        bk = _pick_bk(S)
    assert S % bk == 0, (S, bk)
    qg = q.reshape(B, KV, g, hd)
    scale = hd ** -0.5
    kv_len = jnp.broadcast_to(
        jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))

    grid = (B, KV, S // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, hd), lambda b, h, j, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, bk, hd),
                             lambda b, h, j, *_: (b, h, j, 0)),
                pl.BlockSpec((1, 1, bk, hd),
                             lambda b, h, j, *_: (b, h, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, hd),
                                   lambda b, h, j, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, g, hd), q.dtype),
        interpret=interpret,
    )(kv_len, qg, k, v)
    return out.reshape(B, H, hd)


# ===================================================== pool-layout kernels
# The serve engine's slot pool stores KV as (B, S, KV, hd) — scatters index
# the cache axis right after the slot axis.  These kernels read that layout
# directly, so the hot path never transposes the pool.  A cache block spans
# ALL kv heads, (1, bk, KV, hd): Mosaic tiles the last two block dims, which
# must be divisible by (8, 128) or equal the array's, so a per-head block
# (1, bk, 1, hd) over KV > 1 heads does not compile for a TPU.  The grid is
# (B, cache blocks) and each body walks the heads with a static loop; the
# running (m, l, acc) scratch carries a leading head axis.

def _flash_update(s, v, m_ref, l_ref, acc_ref, h, valid=None):
    """One streaming-softmax accumulator update for kv head ``h``.

    s: (R, BK) masked logits; v: (BK, hd) values; scratch rows
    m/l: (R, 1) and acc: (R, hd) at ``[h]``.  ``valid`` (broadcastable to
    s) zeroes masked probabilities explicitly, for blocks that may hold
    no unmasked logit at all — there the NEG_INF - NEG_INF shift would
    otherwise turn masked entries into exp(0).  A fully-masked block then
    leaves the accumulator bit-identical (alpha == 1, p == 0).
    """
    m_prev = m_ref[h]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[h] = l_ref[h] * alpha + jnp.sum(p, -1, keepdims=True)
    acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[h] = m_new


def _init_scratch(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _finalize(o_ref, acc_ref, l_ref):
    o_ref[0] = (acc_ref[...] /
                jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _slot_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                 *, bk, scale):
    """Full-KV slot decode: per-row valid length, pool layout."""
    ki = pl.program_id(1)
    n_kv, g = q_ref.shape[1], q_ref.shape[2]

    @pl.when(ki == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

    kv_len = len_ref[pl.program_id(0)]

    @pl.when(ki * bk < kv_len)
    def _body():
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (g, bk), 1)
        valid = kpos < kv_len
        for h in range(n_kv):
            q = q_ref[0, h]       # (G, hd)
            k = k_ref[0, :, h]    # (BK, hd)
            v = v_ref[0, :, h]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG_INF)
            _flash_update(s, v, m_ref, l_ref, acc_ref, h)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _fin():
        _finalize(o_ref, acc_ref, l_ref)


def _ring_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                 *, bk, ring, window, scale):
    """Ring-buffer window slot decode, pool layout.

    Each cache slot's ABSOLUTE position is reconstructed from the ring
    invariant (slot ``s`` holds the largest position ``p <= qpos`` with
    ``p % ring == s``) at the row's own length, and the attention band
    ``(qpos - window, qpos]`` is masked on those positions — the in-kernel
    mirror of ``models.attention.ring_slot_attend``.  Rows with
    ``slot_positions < 0`` (done/idle) skip every block and finalize to
    exact zeros.
    """
    ki = pl.program_id(1)
    n_kv, g = q_ref.shape[1], q_ref.shape[2]

    @pl.when(ki == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

    qpos = pos_ref[pl.program_id(0)]  # row length - 1 == query position

    @pl.when(qpos >= 0)
    def _body():
        slot = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (g, bk), 1)
        wrap = qpos // ring  # == (cur_len - 1) // ring, cur_len = qpos + 1
        base = wrap * ring + slot
        kpos = jnp.where(base <= qpos, base, base - ring)
        valid = (kpos >= 0) & (kpos > qpos - window)  # kpos <= qpos
        for h in range(n_kv):
            q = q_ref[0, h]       # (G, hd)
            k = k_ref[0, :, h]    # (BK, hd)
            v = v_ref[0, :, h]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG_INF)
            _flash_update(s, v, m_ref, l_ref, acc_ref, h, valid)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _fin():
        _finalize(o_ref, acc_ref, l_ref)


def _chunk_kernel(off_ref, q_ref, ck_ref, cv_ref, kc_ref, vc_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, bk, nk, g, cache_len, ring,
                  window, scale):
    """Speculative chunk-verify: S = d+1 queries per row over
    [cache ‖ chunk] at per-row offsets, cache READ-ONLY.

    Queries arrive head-major with the chunk and group axes flattened,
    (KV, S * G, hd): row ``r`` is chunk query ``r // G``.  Grid axis 1
    runs nk cache blocks then one chunk step (j == nk): the cache streams
    through VMEM exactly once while all S chunk queries accumulate, and
    the in-flight chunk's own K/V (tiny: S keys) is attended causally in
    the final step.  ``ring`` selects the ring- vs full-layout
    reconstruction of cache key positions; rows with ``offsets < 0``
    (done) produce exact zeros.
    """
    j = pl.program_id(1)
    off = off_ref[pl.program_id(0)]
    n_kv, rows = q_ref.shape[1], q_ref.shape[2]
    s_chunk = rows // g

    @pl.when(j == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

    def band(kpos, width):
        # query row r sits at absolute position off + r // G
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
        qpos = off + (r if g == 1 else r // g)
        v = (kpos >= 0) & (kpos <= qpos)
        if window is not None:
            v &= kpos > qpos - window
        return v

    def attend(k_rows, v_rows, valid):
        for h in range(n_kv):
            q = q_ref[0, h]        # (S*G, hd)
            k = k_rows(h)          # (W, hd)
            v = v_rows(h)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG_INF)
            _flash_update(s, v, m_ref, l_ref, acc_ref, h, valid)

    @pl.when((off >= 0) & (j < nk))
    def _cache_block():
        slot = j * bk + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
        if ring:
            # committed length == off: slot s holds the largest p < off
            # with p % ring == s (never-written slots go negative)
            wrap = (off - 1) // cache_len
            base = wrap * cache_len + slot
            kpos = jnp.where(base < off, base, base - cache_len)
        else:
            kpos = jnp.where(slot < off, slot, -1)
        attend(lambda h: ck_ref[0, :, h], lambda h: cv_ref[0, :, h],
               band(kpos, bk))

    @pl.when((off >= 0) & (j == nk))
    def _chunk_block():
        kpos = off + jax.lax.broadcasted_iota(jnp.int32, (rows, s_chunk), 1)
        # causal within the chunk (a row's own key is always in)
        attend(lambda h: kc_ref[0, :, h], lambda h: vc_ref[0, :, h],
               band(kpos, s_chunk))

    @pl.when(j == nk)
    def _fin():
        _finalize(o_ref, acc_ref, l_ref)


def _scalar_rows(x, B):
    return jnp.broadcast_to(jnp.asarray(x, jnp.int32).reshape(-1), (B,))


def _decode_scratch(KV, g, hd):
    return [pltpu.VMEM((KV, g, 1), jnp.float32),
            pltpu.VMEM((KV, g, 1), jnp.float32),
            pltpu.VMEM((KV, g, hd), jnp.float32)]


def _chunk_q(q, KV):
    """(B, S, H, hd) chunk queries -> head-major (B, KV, S * G, hd)."""
    B, S, H, hd = q.shape
    g = H // KV
    return (q.reshape(B, S, KV, g, hd).transpose(0, 2, 1, 3, 4)
            .reshape(B, KV, S * g, hd))


def _chunk_out(o, S):
    """Inverse of ``_chunk_q`` on the kernel output."""
    B, KV, rows, hd = o.shape
    g = rows // S
    return (o.reshape(B, KV, S, g, hd).transpose(0, 2, 1, 3, 4)
            .reshape(B, S, KV * g, hd))


# ====================================================== paged-pool kernels
# The paged pool stores KV as a shared page arena (n_pages, page, KV, hd)
# plus per-row block tables (B, nblk).  The page INDIRECTION lives entirely
# in the BlockSpec index_map — logical cache block ``j`` of row ``b``
# fetches physical page ``bt[b, j]`` via scalar prefetch — so the kernel
# bodies delegate verbatim to the dense pool-layout bodies above: position
# arithmetic is over LOGICAL blocks and is unchanged.  Sentinel table
# entries (never-allocated blocks, value n_pages) are clamped to the last
# page; the fetched garbage is dropped by the same kv_len/ring/band masks
# that hide the dense pool's unwritten tail.

def _paged_slot_kernel(len_ref, bt_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                       l_ref, acc_ref, *, bk, scale):
    del bt_ref  # consumed by the index_map only
    _slot_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                 acc_ref, bk=bk, scale=scale)


def _paged_ring_kernel(pos_ref, bt_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                       l_ref, acc_ref, *, bk, ring, window, scale):
    del bt_ref
    _ring_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                 acc_ref, bk=bk, ring=ring, window=window, scale=scale)


def _paged_chunk_kernel(off_ref, bt_ref, q_ref, ck_ref, cv_ref, kc_ref,
                        vc_ref, o_ref, m_ref, l_ref, acc_ref, **kw):
    del bt_ref
    _chunk_kernel(off_ref, q_ref, ck_ref, cv_ref, kc_ref, vc_ref, o_ref,
                  m_ref, l_ref, acc_ref, **kw)


def _page_index_map(n_pages, nblk):
    """Cache-operand index_map: logical block j -> physical page bt[b, j]
    (sentinels clamped to the last page; the chunk-verify grid's final
    step overruns to j == nblk and clamps to the last logical block)."""
    def index_map(b, j, scal_ref, bt_ref):
        del scal_ref
        jj = jnp.minimum(j, nblk - 1)
        return (jnp.minimum(bt_ref[b, jj], n_pages - 1), 0, 0, 0)
    return index_map


def _row_map(b, j, *_):
    return (b, 0, 0, 0)


def _decode_call(kernel, scalars, bt, q, k, v, *, bk, nblk, index_map,
                 interpret):
    """One pool-layout decode ``pallas_call``: q (B, H, hd) against
    (·, bk, KV, hd) cache blocks fetched by ``index_map`` over a
    (B, nblk) grid; ``bt`` (paged) rides as a second scalar prefetch."""
    B, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    prefetch = (scalars,) if bt is None else (scalars, bt.astype(jnp.int32))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B, nblk),
            in_specs=[
                pl.BlockSpec((1, KV, g, hd), _row_map),
                pl.BlockSpec((1, bk, KV, hd), index_map),
                pl.BlockSpec((1, bk, KV, hd), index_map),
            ],
            out_specs=pl.BlockSpec((1, KV, g, hd), _row_map),
            scratch_shapes=_decode_scratch(KV, g, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, g, hd), q.dtype),
        interpret=interpret,
    )(*prefetch, q.reshape(B, KV, g, hd), k, v)
    return out.reshape(B, H, hd)


def _verify_call(kernel, offsets, bt, q, ck, cv, k, v, *, bk, nk,
                 index_map, interpret):
    """One pool-layout chunk-verify ``pallas_call`` over a (B, nk + 1)
    grid: nk cache blocks via ``index_map``, then the chunk step."""
    B, S, H, hd = q.shape
    KV = ck.shape[2]
    rows = S * (H // KV)
    prefetch = (offsets,) if bt is None else (offsets, bt.astype(jnp.int32))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B, nk + 1),
            in_specs=[
                pl.BlockSpec((1, KV, rows, hd), _row_map),
                pl.BlockSpec((1, bk, KV, hd), index_map),
                pl.BlockSpec((1, bk, KV, hd), index_map),
                pl.BlockSpec((1, S, KV, hd), _row_map),
                pl.BlockSpec((1, S, KV, hd), _row_map),
            ],
            out_specs=pl.BlockSpec((1, KV, rows, hd), _row_map),
            scratch_shapes=_decode_scratch(KV, rows, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, rows, hd), q.dtype),
        interpret=interpret,
    )(*prefetch, _chunk_q(q, KV), ck, cv, k, v)
    return _chunk_out(out, S)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_slot_decode_attention(q, k, v, bt, kv_len, *, interpret=False):
    """``slot_decode_attention`` over a page arena.

    q: (B, H, hd); k, v: (n_pages, page, KV, hd) shared arenas; bt:
    (B, nblk) int32 block tables (page ids; n_pages = OOB sentinel);
    kv_len: (B,) valid lengths.  The block size is pinned to the page —
    pages are only contiguous within themselves.  Returns (B, H, hd).
    """
    n_pages, page = k.shape[0], k.shape[1]
    nblk = bt.shape[1]
    hd = q.shape[2]
    return _decode_call(
        functools.partial(_paged_slot_kernel, bk=page, scale=hd ** -0.5),
        _scalar_rows(kv_len, q.shape[0]), bt, q, k, v, bk=page, nblk=nblk,
        index_map=_page_index_map(n_pages, nblk), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_ring_decode_attention(q, k, v, bt, slot_positions, *, window,
                                interpret=False):
    """``ring_decode_attention`` over a page arena.

    The ring modulus is the LOGICAL length ``nblk * page``; ring slot
    ``s`` of row ``b`` lives at ``arena[bt[b, s // page], s % page]``.
    slot_positions: (B,) query positions, -1 for done rows.
    """
    n_pages, page = k.shape[0], k.shape[1]
    nblk = bt.shape[1]
    hd = q.shape[2]
    return _decode_call(
        functools.partial(_paged_ring_kernel, bk=page, ring=nblk * page,
                          window=window, scale=hd ** -0.5),
        _scalar_rows(slot_positions, q.shape[0]), bt, q, k, v, bk=page,
        nblk=nblk, index_map=_page_index_map(n_pages, nblk),
        interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("ring", "window", "interpret"))
def paged_chunk_verify_attention(q, ck, cv, bt, k, v, offsets, *, ring,
                                 window=None, interpret=False):
    """``chunk_verify_attention`` over a page arena (cache read-only).

    ck, cv: (n_pages, page, KV, hd) arenas; bt: (B, nblk); the logical
    cache length is ``nblk * page``.  Grid axis 1 runs the nblk cache
    blocks then one chunk step — the cache index_map clamps the chunk
    step's overrun to the last logical block before resolving the page.
    """
    n_pages, page, KV = ck.shape[0], ck.shape[1], ck.shape[2]
    nblk = bt.shape[1]
    hd = q.shape[3]
    return _verify_call(
        functools.partial(_paged_chunk_kernel, bk=page, nk=nblk,
                          g=q.shape[2] // KV, cache_len=nblk * page,
                          ring=ring, window=window, scale=hd ** -0.5),
        _scalar_rows(offsets, q.shape[0]), bt, q, ck, cv, k, v, bk=page,
        nk=nblk, index_map=_page_index_map(n_pages, nblk),
        interpret=interpret)


def _block_map(nk):
    """Dense cache-operand index_map: block j of row b (the chunk-verify
    grid's final step overruns to j == nk and clamps to the last block)."""
    def index_map(b, j, *_):
        return (b, jnp.minimum(j, nk - 1), 0, 0)
    return index_map


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def slot_decode_attention(q, k, v, kv_len, *, bk=None, interpret=False):
    """Full-KV slot decode in POOL layout.

    q: (B, H, hd); k, v: (B, S, KV, hd) — the serve pool's native layout;
    kv_len: (B,) per-row valid lengths (0 = idle/done row -> exact zeros).
    Returns (B, H, hd).
    """
    S, hd = k.shape[1], q.shape[2]
    if bk is None:
        bk = _pick_bk(S)
    assert S % bk == 0, (S, bk)
    return _decode_call(
        functools.partial(_slot_kernel, bk=bk, scale=hd ** -0.5),
        _scalar_rows(kv_len, q.shape[0]), None, q, k, v, bk=bk,
        nblk=S // bk, index_map=_block_map(S // bk), interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("window", "bk", "interpret"))
def ring_decode_attention(q, k, v, slot_positions, *, window, bk=None,
                          interpret=False):
    """Ring-buffer window slot decode in POOL layout.

    q: (B, H, hd); k, v: (B, ring, KV, hd) ring caches that already hold
    this step's K/V at ``slot_positions % ring``; slot_positions: (B,)
    per-row query positions (== row length - 1 after the write), -1 for
    done/idle rows (exact-zero output).  ``window`` is the attention band;
    the ring modulus is the cache length itself (>= window once padded).
    Returns (B, H, hd).
    """
    ring, hd = k.shape[1], q.shape[2]
    if bk is None:
        bk = _pick_bk(ring)
    assert ring % bk == 0, (ring, bk)
    return _decode_call(
        functools.partial(_ring_kernel, bk=bk, ring=ring, window=window,
                          scale=hd ** -0.5),
        _scalar_rows(slot_positions, q.shape[0]), None, q, k, v, bk=bk,
        nblk=ring // bk, index_map=_block_map(ring // bk),
        interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("ring", "window", "bk", "interpret"))
def chunk_verify_attention(q, ck, cv, k, v, offsets, *, ring, window=None,
                           bk=None, interpret=False):
    """Speculative chunk-verify attention in POOL layout.

    q: (B, S, H, hd) — the D+1-token verify chunk's queries; ck, cv:
    (B, Sc, KV, hd) read-only cache (full prefix or ring buffer — pick
    with the static ``ring`` flag); k, v: (B, S, KV, hd) the chunk's own
    K/V; offsets: (B,) per-row committed lengths (-1 = done row -> exact
    zeros).  ``window`` adds the sliding-window band.  Returns
    (B, S, H, hd); the cache operands are never written.
    """
    Sc, KV = ck.shape[1], ck.shape[2]
    hd = q.shape[3]
    if bk is None:
        bk = _pick_bk(Sc)
    assert Sc % bk == 0, (Sc, bk)
    nk = Sc // bk
    return _verify_call(
        functools.partial(_chunk_kernel, bk=bk, nk=nk, g=q.shape[2] // KV,
                          cache_len=Sc, ring=ring, window=window,
                          scale=hd ** -0.5),
        _scalar_rows(offsets, q.shape[0]), None, q, ck, cv, k, v, bk=bk,
        nk=nk, index_map=_block_map(nk), interpret=interpret)
