"""Fused attention — Pallas TPU kernels, forward AND backward.

**Beyond-reference native kernel** (the reference's native surface was
CUDA elementwise strings — SURVEY.md §2.3; this is the TPU analogue for
the attention hot op used by the sequence-parallel extension).

Forward: K/V-STREAMING grid (round 3) — grid (batch*head, q-tile,
k-tile): the q tile and the online-softmax accumulators (acc, running
max, denominator) live in VMEM scratch across the k-tile grid steps,
while each K/V TILE is fetched by the Pallas pipeline per step.  VMEM
residency is O(block) rather than O(T), which lifts the previous
full-sequence-resident bound (~T=12k at D=128) to HBM capacity; the
pipelined tile fetches overlap the MXU matmuls.  The softmax is online
(never a full [T, T] score matrix anywhere); the per-row logsumexp is
written out as a residual so the backward never re-derives it.

Backward: two streaming Pallas kernels in the standard flash-gradient
shape — grid (bh, k-tile, q-tile) accumulating (dk, dv) in scratch
while q/dO/lse/delta tiles stream, and grid (bh, q-tile, k-tile)
accumulating dq while K/V tiles stream — each recomputing its score
tile from q/k and the saved logsumexp, so the [T, T] matrix is
materialized in NEITHER direction and VMEM stays O(block) end to end.
A pure-XLA blockwise backward with identical math is kept
(``bwd_impl="blockwise"``) as the cross-check oracle for the
gradient-parity tests.

Tile classes (PR 33).  Where the mask follows from shapes and the grid
indices alone — ``causal``, positions from 0 on both sides (no offsets), no
segment ids, no dropout, ``Tq == Tkv`` and ``block_q == block_k`` — the three
kernels know each visited tile's class from ``rel = q tile - k tile``
(:func:`_tile_plan`) and do the work of its class:

* INTERIOR (every pair visible): the tile's body with no mask, no iota and
  no select.
* EDGE (the causal diagonal, or a window's far edge, crosses the tile): the
  tile is walked as sub-tiles of ``_SUB x _SUB`` pairs; a sub-tile with no
  visible pair does nothing, whole sub-tiles side by side are one product,
  and only a sub-tile an edge crosses is masked.  The forward advances the
  online softmax once a sub-tile row, ``dq`` adds by sub-tile row and
  ``dk, dv`` by sub-tile column.  Which sub-tiles run is static per class.
* OUTSIDE (no visible pair): no work (``pl.when`` is false).

Anywhere else a tile's class is not static (offsets are runtime values, a
segment or dropout mask touches every tile) and every visited tile takes the
GENERIC body: the whole tile under a mask built from global positions.
:func:`flash_tile_census` counts, from the same arithmetic, what the classes
take away.

Masking and dropout:

* ``causal`` — lower-triangular mask; fully-masked K/V tiles are
  skipped (forward) / never visited (backward).
* ``window`` (with ``causal``) — a sliding window: row i sees column j
  only where ``0 <= i - j < window``.  A tile with no visible pair does no
  work on EITHER side of the band; without position offsets (which neither
  the grid nor an index map can see) the streamed grid dimension walks the
  band's tiles alone (:func:`_band`), so the others are neither fetched nor
  stepped over.  ``window=None``, and without offsets a window as long as
  the queries, is plain causal attention and traced as such.
* ``q_segment_ids``/``kv_segment_ids`` ([B, T] int32) — attention is
  allowed only where the ids match, which expresses packed-sequence and
  padding masks (give padding a sentinel id that matches nothing).
  Fully-masked rows produce zero output and zero gradients.
* ``dropout_rate``/``dropout_seed`` — attention-weight dropout applied
  after normalization with inverted scaling (kept weights / keep_p).
  The mask is a counter-based hash of (seed, batch*head, q_pos, k_pos)
  computed identically in forward, backward, and the blockwise oracle —
  nothing random is stored, so the recompute-based backward stays exact.

Callers: the LM families' attention layers (single shard, the benchmark's
cells), :func:`chainermn_tpu.parallel.sequence.ring_attention` /
``ulysses_attention`` (pass ``attn_fn=flash_attention``; ring attention
passes offsets) and the serving engine's decode over a paged cache (per-row
offsets, ``Tq != Tkv``).  Off-TPU the kernels run in Pallas interpret mode
so the CPU test mesh exercises the same code path.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

try:  # pltpu only imports on TPU-capable installs; interpret mode needs it not
    from jax.experimental.pallas import tpu as pltpu
    _VMEM = pltpu.VMEM
except Exception:  # pragma: no cover
    pltpu = None
    _VMEM = None

_BLOCK_Q = 1024  # what a grid step costs is paid once a tile: PERF.md section 5
_BLOCK_K = 1024  # has the cells' kernel times at this size, section 7 what is open
# An EDGE tile (the causal diagonal or a window's far edge crosses it) is
# walked as sub-tiles of _SUB x _SUB pairs, and a sub-tile that holds no
# visible pair does nothing.  One value for the three kernels and every
# caller, chosen from a sweep on the chip (PERF.md section 6, PR 33).
_SUB = 512
_NEG_INF = -1e30
_LSE_SENTINEL = 1e30  # lse for fully-masked rows: exp(s - sentinel) == 0


def _keep_mask(seed_u32, bh_idx, q_pos, k_pos, rate):
    """Deterministic dropout keep-mask from a counter-based hash.

    ``q_pos``/``k_pos`` are GLOBAL positions (broadcastable int32
    arrays), so forward and backward — which tile the [T, T] plane
    differently — reproduce the identical mask.  Murmur3-finalizer
    rounds give well-mixed bits from pure uint32 VPU arithmetic (no
    stateful PRNG, works under both compiled and interpret modes).
    """
    x = (q_pos.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         ^ k_pos.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
         ^ (bh_idx.astype(jnp.uint32) if hasattr(bh_idx, "astype")
            else jnp.uint32(bh_idx)) * jnp.uint32(0xC2B2AE35)
         ^ seed_u32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    thresh = min(int(rate * 2 ** 32), 2 ** 32 - 1)
    return x >= jnp.uint32(thresh)


def _shape_like(template, shape, dtype):
    """ShapeDtypeStruct carrying ``template``'s varying-axes (vma) metadata
    — needed for shard_map composition."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(template).vma)


def _unpack_rest(rest, has_seg, dropout_rate, has_offsets=False):
    """Split a kernel's trailing refs into (qseg, kseg, seed, offs, outputs)
    — shared by all three kernels so the optional-input threading lives
    once."""
    idx = 0
    qseg_ref = kseg_ref = seed_ref = offs_ref = None
    if has_seg:
        qseg_ref, kseg_ref = rest[0], rest[1]
        idx = 2
    if dropout_rate > 0.0:
        seed_ref = rest[idx]
        idx += 1
    if has_offsets:
        offs_ref = rest[idx]
        idx += 1
    return qseg_ref, kseg_ref, seed_ref, offs_ref, rest[idx:]


def _mask_tile(causal, q_pos, k_pos, seg_q, seg_k, window=None):
    """[bq, bk] bool allow-mask (or None when nothing masks)."""
    mask = None
    if causal:
        mask = q_pos >= k_pos
        if window is not None:
            mask = mask & (q_pos - k_pos < window)
    if seg_q is not None:
        m2 = seg_q[:, None] == seg_k[None, :]
        mask = m2 if mask is None else (mask & m2)
    return mask


def _tile_runs(causal, window, q_first, bq, k_first, bk, always):
    """Whether the tile of query rows ``q_first ... q_first + bq - 1`` and
    key rows ``k_first ... k_first + bk - 1`` (global positions) holds a
    visible pair: its last row sees the first key (causal), and its first
    row is nearer than ``window`` to the last key."""
    if not causal:
        return always
    run = q_first + bq - 1 >= k_first
    if window is not None:
        run = run & (q_first - (k_first + bk - 1) < window)
    return run


def _visible(gap_min, gap_max, window):
    """What a block of pairs whose ``row - column`` runs from ``gap_min`` to
    ``gap_max`` holds under ``0 <= row - column < window``: ``None`` where no
    pair is visible, else ``(causal, far)``: whether the diagonal crosses
    the block (some gap is negative) and whether the window's far edge does
    (some gap reaches ``window``).  ``(False, False)``: every pair visible.
    The arithmetic of :func:`_tile_runs`, on Python integers."""
    if gap_max < 0 or (window is not None and gap_min >= window):
        return None
    return gap_min < 0, window is not None and gap_max >= window


class _Piece(NamedTuple):
    """Part of a strip of an edge tile: the elements ``start ... stop - 1``
    along the strip, visible where ``lo <= row - column < hi`` in the piece's
    own coordinates (``None``: that side needs no test).  Whole sub-tiles
    that lie side by side are one piece with no test; a sub-tile an edge
    crosses is a piece of its own."""

    start: int
    stop: int
    lo: Optional[int] = None
    hi: Optional[int] = None

    @property
    def every_row_sees(self):
        """Whether the piece's own diagonal (``row == column``) is visible:
        then every row of it sees a pair, whatever it saw before."""
        return ((self.lo is None or self.lo <= 0)
                and (self.hi is None or self.hi > 0))


def _strip_pieces(gaps, sub, window):
    """The pieces of one strip: ``gaps[i]`` is ``row - column`` between the
    first row and the first column of the strip's i-th sub-tile."""
    pieces = []
    for i, gap in enumerate(gaps):
        seen = _visible(gap - (sub - 1), gap + sub - 1, window)
        if seen is None:
            continue
        causal, far = seen
        piece = _Piece(i * sub, (i + 1) * sub, -gap if causal else None,
                       window - gap if far else None)
        whole = piece.lo is None and piece.hi is None
        if (whole and pieces and pieces[-1].stop == piece.start
                and pieces[-1].lo is None and pieces[-1].hi is None):
            piece = _Piece(pieces.pop().start, piece.stop)
        pieces.append(piece)
    return tuple(pieces)


class _TilePlan(NamedTuple):
    """The classes of a call's tiles by ``rel = q tile - k tile`` (both
    sides tiled alike from position 0): INTERIOR for ``interior[0] <= rel <=
    interior[1]`` (every pair visible: the body with no mask), EDGE for the
    keys of ``rows`` / ``cols`` (for each sub-tile row, or column, the
    pieces of its strip that hold a visible pair), OUTSIDE otherwise."""

    sub: int
    interior: tuple
    rows: dict
    cols: dict


def _sub_of(block, sub):
    """The sub-tile a tile of ``block`` is walked in: ``sub`` where it
    divides the tile, else the whole tile."""
    return sub if block % sub == 0 else block


def _tile_plan(causal, window, has_offsets, has_seg, dropout_rate, tq, tk,
               bq, bk, sub) -> Optional[_TilePlan]:
    """A tile's class is static only where the mask follows from shapes and
    the grid indices alone: causal, positions from 0 on both sides (no
    offsets: runtime values), no segment ids and no dropout (they touch every
    tile), both sides tiled alike.  Elsewhere ``None``: every visited tile
    takes the generic masked body."""
    if (not causal or has_offsets or has_seg or dropout_rate > 0.0
            or tq != tk or bq != bk):
        return None
    sub = _sub_of(bq, sub)
    n_sub = bq // sub
    interior, rows, cols = [], {}, {}
    for rel in range(tq // bq):
        seen = _visible(rel * bq - (bk - 1), rel * bq + bq - 1, window)
        if seen is None:
            continue
        if seen == (False, False):
            interior.append(rel)
            continue
        gap = lambda a, c: rel * bq + (a - c) * sub
        rows[rel] = tuple(
            _strip_pieces([gap(a, c) for c in range(n_sub)], sub, window)
            for a in range(n_sub))
        # a column's strip runs along the rows: the same test, with the
        # piece's own row - column measured from the piece's first row
        cols[rel] = tuple(
            _strip_pieces([gap(a, c) for a in range(n_sub)], sub, window)
            for c in range(n_sub))
    span = (interior[0], interior[-1]) if interior else (1, 0)
    return _TilePlan(sub, span, rows, cols)


def _piece_mask(piece, gap):
    """The allow-mask of a piece an edge crosses (``gap`` = the piece's own
    ``row - column``), or ``None`` where every pair is visible."""
    mask = None
    if piece.lo is not None:
        mask = lax.ge(gap, np.int32(piece.lo))
    if piece.hi is not None:
        far = lax.lt(gap, np.int32(piece.hi))
        mask = far if mask is None else lax.bitwise_and(mask, far)
    return mask


def _where(mask, x, otherwise):
    """``jnp.where(mask, x, otherwise)`` for a scalar ``otherwise``."""
    return lax.select(mask, x, lax.full_like(x, otherwise))


def _row_reduce(reduce, x):
    """``reduce`` over each row of ``x``, kept as a column ``[rows, 1]``."""
    return lax.broadcast_in_dim(reduce(x, (1,)), (x.shape[0], 1), (0,))


def _effective_window(window, has_offsets, tq):
    """With positions from 0 on both sides no row is ``tq`` or more past a
    column, so a window that long bounds nothing: the call IS plain causal
    attention and is traced as such."""
    if window is not None and not has_offsets and window >= tq:
        return None
    return window


def _first_k_tile(j, bq, bk, window):
    """The first k tile that holds a pair visible to query tile ``j``
    (positions from 0 on both sides); ``j`` a Python or a traced integer."""
    largest = max if isinstance(j, int) else jnp.maximum
    return largest(j * bq - (window - 1), 0) // bk


def _first_q_tile(j, bq, bk):
    """The first q tile that sees a key of k tile ``j``."""
    return (j * bk) // bq


class _Band(NamedTuple):
    """How the streamed grid dimension walks the tiles: ``k_*`` for the
    kernels whose steps hold k tiles (forward, ``dq``), ``q_*`` for the one
    whose steps hold q tiles (``dk, dv``).  ``*_steps`` is the dimension's
    size, ``*_of(j, step)`` the tile that tile j's ``step`` holds (for an
    index map), ``*_kwargs`` what the kernel is told."""

    k_steps: int
    k_of: Callable
    k_kwargs: dict
    q_steps: int
    q_of: Callable
    q_kwargs: dict


def _band(window, has_offsets, bq, bk, n_q, n_k, classified) -> _Band:
    """With no window every step holds its own tile, all ``n_k`` (``n_q``)
    of them, and the kernels get no keyword they did not always have; where
    the call is ``classified`` (:func:`_tile_plan`: the tiles past the
    diagonal are known to do nothing) the index maps hold the diagonal's tile
    through those steps, and an index that repeats fetches nothing.  Under
    a window they get ``window``, mask by it and skip a tile with no
    visible pair.  Where positions start at 0 on both sides (no offsets:
    neither the grid nor an index map can see them) the grid is BANDED
    besides: query tile j's steps walk the k tiles from ``_first_k_tile(j)``
    on, as many as the widest band needs, so tiles outside the band are
    neither fetched nor stepped over; where a band runs off the end the
    index map repeats the last tile (an index that repeats fetches nothing)
    and the kernel, told ``n_tiles``, skips the step."""
    own = lambda j, step: step
    if window is None or has_offsets:
        kwargs = {} if window is None else {"window": window}
        if classified:
            return _Band(n_k, lambda j, step: jnp.minimum(step, j), kwargs,
                         n_q, lambda j, step: jnp.maximum(step, j), kwargs)
        return _Band(n_k, own, kwargs, n_q, own, kwargs)
    k_steps = max(min((j * bq + bq - 1) // bk, n_k - 1)
                  - _first_k_tile(j, bq, bk, window) + 1 for j in range(n_q))
    q_steps = max(min((j * bk + bk - 2 + window) // bq, n_q - 1)
                  - min(_first_q_tile(j, bq, bk), n_q - 1) + 1
                  for j in range(n_k))
    kwargs = {"window": window, "banded": True}
    return _Band(
        k_steps, lambda j, s: jnp.minimum(
            _first_k_tile(j, bq, bk, window) + s, n_k - 1),
        dict(kwargs, n_tiles=n_k),
        q_steps, lambda j, s: jnp.minimum(
            _first_q_tile(j, bq, bk) + s, n_q - 1),
        dict(kwargs, n_tiles=n_q))


def _generic_masks(masked, causal, window, has_seg, dropout_rate, qseg_ref,
                   kseg_ref, seed, bh_idx, q_first, bq, k_first, bk):
    """The whole tile's allow-mask and (keep-mask, 1 / keep probability) by
    global position — the generic body's, for any call; ``(None, None)``
    where the tile is not ``masked`` (interior) or nothing masks."""
    if not (masked and (causal or has_seg or dropout_rate > 0.0)):
        return None, None
    q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    seg_q = qseg_ref[0, 0] if has_seg else None
    seg_k = kseg_ref[0, 0] if has_seg else None
    mask = _mask_tile(causal, q_pos, k_pos, seg_q, seg_k, window)
    keep_inv = None
    if dropout_rate > 0.0:
        keep_inv = (_keep_mask(seed, bh_idx, q_pos, k_pos, dropout_rate),
                    1.0 / (1.0 - dropout_rate))
    return mask, keep_inv


def _sub_gap(sub):
    """``row - column`` inside a sub-tile: what an edge's mask tests."""
    return lax.sub(lax.broadcasted_iota(jnp.int32, (sub, sub), 0),
                   lax.broadcasted_iota(jnp.int32, (sub, sub), 1))


def _when_classified(classes, run, inside, rel, tile, edge):
    """Run the body of this step's class.  ``classes`` is ``None`` (no plan:
    ``tile(True)``, the whole tile under the generic mask, wherever ``run``
    says the tile holds a visible pair) or a plan's ``(interior, edges)``:
    ``tile(False)`` on an interior tile and ``edge(strips)`` on an edge tile,
    by ``rel``, where the step's tile lies ``inside`` the sequence."""
    if classes is None:
        pl.when(run & inside)(lambda: tile(True))
        return
    (first, last), edges = classes
    if first <= last:
        pl.when((rel >= first) & (rel <= last) & inside)(
            lambda: tile(False))
    for edge_rel, strips in edges.items():
        pl.when((rel == edge_rel) & inside)(functools.partial(edge, strips))


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale, causal,
                has_seg, dropout_rate, has_offsets, window=None,
                banded=False, n_tiles=None, plan=None):
    # Streaming grid (bh, q-tile, k-tile): q_ref [1, BQ, D] (fixed per
    # (bh, j)); k_ref [1, BK, D], v_ref [1, BK, Dv] = THIS grid step's tile;
    # optional qseg [1, 1, BQ], kseg [1, 1, BK], seed [1, 1], offs [1, 2];
    # outputs o [1, BQ, Dv], lse [1, 1, BQ] (written at the last k step);
    # scratch acc [BQ, Dv], m [BQ, 1], l [BQ, 1] persist across the k
    # dimension.
    qseg_ref, kseg_ref, seed_ref, offs_ref, rest = _unpack_rest(
        rest, has_seg, dropout_rate, has_offsets)
    o_ref, lse_ref, acc_s, m_s, l_s = rest

    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    kk = pl.program_id(2)
    n_k = pl.num_programs(2)
    q_off = pl.program_id(1) * bq
    # banded: step kk holds the kk-th k tile of this q tile's band
    k_tile = (_first_k_tile(pl.program_id(1), bq, bk, window) + kk
              if banded else kk)
    k_off = k_tile * bk
    bh_idx = pl.program_id(0)
    seed = seed_ref[0, 0].astype(jnp.uint32) if seed_ref is not None else None
    # global position offsets (ring-attention blocks of a longer sequence)
    goff_q = offs_ref[0, 0] if has_offsets else 0
    goff_k = offs_ref[0, 1] if has_offsets else 0

    @pl.when(kk == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    # (the bodies below bind lax primitives themselves: an edge tile unrolls
    # them a dozen times a kernel, a step traces thirty kernels and more,
    # and a jnp call costs several times the tracing of the primitive it
    # binds, which is set-up time)
    def scores(q, k):
        # scale after the matmul — same op order as the unfused reference,
        # so results match it to tight tolerance
        return lax.mul(lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                       preferred_element_type=jnp.float32),
                       sm_scale)

    def advance(rows, parts):
        """One online-softmax step of the query rows ``rows`` over ``parts``:
        (scores, allow-mask or None, whether a row of it may see nothing so
        far, (keep-mask, 1 / keep probability) or None, the value rows)."""
        m = m_s[rows]
        m_new = m
        for s, *_ in parts:
            m_new = lax.max(m_new, _row_reduce(lax.reduce_max, s))
        alpha = lax.exp(lax.sub(m, m_new))
        l = lax.mul(l_s[rows], alpha)
        acc = lax.mul(acc_s[rows], alpha)
        for s, mask, may_be_empty, keep_inv, v in parts:
            p = lax.exp(lax.sub(s, m_new))
            if mask is not None and may_be_empty:
                # when a whole row is masked so far, s - m_new == 0 and exp
                # would give 1 — zero the masked entries explicitly
                p = _where(mask, p, 0.0)
            l = lax.add(l, _row_reduce(lax.reduce_sum, p))
            if keep_inv is not None:
                p = _where(keep_inv[0], lax.mul(p, keep_inv[1]), 0.0)
            acc = lax.add(acc, lax.dot_general(
                lax.convert_element_type(p, v.dtype), v,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        l_s[rows] = l
        m_s[rows] = m_new
        acc_s[rows] = acc

    def _tile(masked):
        # the whole tile in one step; ``masked``: under the generic mask
        # (causal and window by global position, segment ids) and dropout
        mask, keep_inv = _generic_masks(
            masked, causal, window, has_seg, dropout_rate, qseg_ref, kseg_ref,
            seed, bh_idx, goff_q + q_off, bq, goff_k + k_off, bk)
        s = scores(q_ref[0], k_ref[0])
        if mask is not None:
            s = _where(mask, s, _NEG_INF)
        advance(slice(None), [(s, mask, True, keep_inv, v_ref[0])])

    def _edge(strips):
        # each sub-tile row advances once, over the pieces of its strip
        # that hold a visible pair; only a piece an edge crosses is masked
        gap = _sub_gap(plan.sub)
        for a, pieces in enumerate(strips):
            if not pieces:
                continue
            rows = slice(a * plan.sub, (a + 1) * plan.sub)
            q = q_ref[0, rows, :]
            parts = []
            for piece in pieces:
                cols = slice(piece.start, piece.stop)
                s = scores(q, k_ref[0, cols, :])
                mask = _piece_mask(piece, gap)
                if mask is not None:
                    s = _where(mask, s, _NEG_INF)
                parts.append((s, mask, not piece.every_row_sees, None,
                              v_ref[0, cols, :]))
            advance(rows, parts)

    # full-tile skip: the tile contributes only if some q row can see its
    # first k row (the fetch still pipelines; the MXU work is skipped), and
    # under a window only if its last k row is near enough to the first q
    run = _tile_runs(causal, window, goff_q + q_off, bq, goff_k + k_off, bk,
                     kk >= 0)
    # a band that runs off the end of the sequence holds no tile there
    inside = k_tile < n_tiles if banded else True
    _when_classified(plan and (plan.interior, plan.rows), run, inside,
                     pl.program_id(1) - k_tile, _tile, _edge)

    @pl.when(kk == n_k - 1)
    def _finish():
        l = l_s[...]
        empty = l == 0.0
        o_ref[0] = (acc_s[...] / jnp.where(empty, 1.0, l)).astype(
            o_ref.dtype)
        lse = jnp.where(empty[:, 0], _LSE_SENTINEL,
                        m_s[...][:, 0] + jnp.log(
                            jnp.where(empty[:, 0], 1.0, l[:, 0])))
        lse_ref[0, 0] = lse.astype(jnp.float32)


def _scratch(shapes_dtypes):
    """VMEM scratch allocations — the accumulators that persist across the
    streaming grid dimension (interpret mode allocates them as arrays).
    Installs without pltpu (pure-CPU jax) fall back to the memory-space-
    agnostic MemoryRef, which the interpreter accepts."""
    if pltpu is not None:
        return [pltpu.VMEM(s, dt) for s, dt in shapes_dtypes]
    return [pl.MemoryRef(jax.core.ShapedArray(s, dt), pl.ANY)
            for s, dt in shapes_dtypes]


def _fold(x):
    """[B, T, H, D] -> [B*H, T, D], whatever the head size (q's and k's, or
    v's own)."""
    return x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1], x.shape[3])


def _forward(q, k, v, qseg, kseg, seed, offs, causal, sm_scale, block_q,
             block_k, dropout_rate, interpret, window=None):
    b, tq, h, d = q.shape
    tk, hk = k.shape[1], k.shape[2]
    d_v = v.shape[3]  # the value (and output) head size; d is q's and k's
    grp = h // hk  # q heads per kv head (1 = MHA; >1 = GQA/MQA)
    scale = sm_scale if sm_scale is not None else d ** -0.5
    bq = min(block_q, tq)
    bk = min(block_k, tk)
    if tq % bq or tk % bk:
        raise ValueError(
            f"flash_attention needs seq lens ({tq}, {tk}) divisible by "
            f"their tiles ({bq}, {bk}); pad the sequence or pass smaller "
            f"block sizes")
    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    # grid dim 0 iterates q heads (b*h programs); a kv tensor row for
    # program i is its (batch, kv-head) pair
    kv_row = lambda i: (i // h) * hk + (i % h) // grp
    has_seg = qseg is not None
    has_offsets = offs is not None
    window = _effective_window(window, has_offsets, tq)

    # the k tiles a q tile's steps hold: all of them, or a window's band
    plan = _tile_plan(causal, window, has_offsets, has_seg, dropout_rate,
                      tq, tk, bq, bk, _SUB)
    band = _band(window, has_offsets, bq, bk, tq // bq, tk // bk,
                 plan is not None)
    k_steps, k_of = band.k_steps, band.k_of
    kern = functools.partial(_fwd_kernel, sm_scale=scale, causal=causal,
                             has_seg=has_seg, dropout_rate=dropout_rate,
                             has_offsets=has_offsets, plan=plan,
                             **band.k_kwargs)
    kw = {} if _VMEM is None else {"memory_space": _VMEM}
    ins = [qf, kf, vf]
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, 0), **kw),
        pl.BlockSpec((1, bk, d),
                     lambda i, j, kk: (kv_row(i), k_of(j, kk), 0), **kw),
        pl.BlockSpec((1, bk, d_v),
                     lambda i, j, kk: (kv_row(i), k_of(j, kk), 0), **kw),
    ]
    if has_seg:
        # segment ids are per-batch; heads share them (index map i // h).
        # TPU tiling wants the last two block dims divisible by (8, 128) or
        # equal to the array dims — a singleton row dim satisfies that, so
        # host-side vectors ride as [*, 1, T].
        ins += [qseg.reshape(b, 1, tq), kseg.reshape(b, 1, tk)]
        in_specs += [
            pl.BlockSpec((1, 1, bq), lambda i, j, kk: (i // h, 0, j), **kw),
            pl.BlockSpec((1, 1, bk),
                         lambda i, j, kk: (i // h, 0, k_of(j, kk)), **kw),
        ]
    if dropout_rate > 0.0:
        ins.append(seed.reshape(1, 1))
        in_specs.append(pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0), **kw))
    if has_offsets:
        # offs is [B, 2] (per-sequence global positions); each program
        # reads its batch row — a [1, 2] block, like the seg-id vectors.
        ins.append(offs)
        in_specs.append(
            pl.BlockSpec((1, 2), lambda i, j, kk: (i // h, 0), **kw))
    # Inside shard_map the outputs must carry the inputs' varying-axes
    # metadata (vma) so the kernel composes with sequence parallelism.
    out_shape = [_shape_like(qf, (b * h, tq, d_v), q.dtype),
                 _shape_like(qf, (b * h, 1, tq), jnp.float32)]
    out, lse = pl.pallas_call(
        kern,
        grid=(b * h, tq // bq, k_steps),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d_v), lambda i, j, kk: (i, j, 0), **kw),
            pl.BlockSpec((1, 1, bq), lambda i, j, kk: (i, 0, j), **kw)],
        out_shape=out_shape,
        scratch_shapes=_scratch([((bq, d_v), jnp.float32),
                                 ((bq, 1), jnp.float32),
                                 ((bq, 1), jnp.float32)]),
        interpret=interpret,
    )(*ins)
    return out.reshape(b, h, tq, d_v).transpose(0, 2, 1, 3), lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _score_grads(q, g, k, v, lse, delta, glse, sm_scale, mask, keep_inv):
    """What both backward kernels recompute for a block of pairs: the
    normalized probabilities ``a`` (dropped where ``keep_inv`` = (keep-mask,
    1 / keep probability) says: ``a_drop``) and the scores' gradient ``ds``.
    ``lse`` / ``delta`` / ``glse`` are the rows' vectors."""
    # (lax primitives, not jnp calls: see the forward kernel's note)
    column = lambda vec: lax.broadcast_in_dim(vec, (vec.shape[0], 1), (0,))
    s = lax.mul(lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32), sm_scale)
    a = lax.exp(lax.sub(s, column(lse)))              # normalized probs
    if mask is not None:
        a = _where(mask, a, 0.0)
    dp = lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    if keep_inv is not None:
        keep, inv = keep_inv
        a_drop = _where(keep, lax.mul(a, inv), 0.0)
        da = _where(keep, lax.mul(dp, inv), 0.0)
    else:
        a_drop = a
        da = dp
    ds = lax.mul(lax.mul(a, lax.sub(da, column(delta))), sm_scale)
    if glse is not None:
        # cotangent flowing into the logsumexp output: d lse_i / d s_ij
        # = a_ij (same a as above), in scaled-score space
        ds = lax.add(ds, lax.mul(lax.mul(a, column(glse)), sm_scale))
    return a_drop, ds


def _dkv_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref, *rest,
                sm_scale, causal, has_seg, dropout_rate,
                has_offsets, with_lse, window=None, banded=False,
                n_tiles=None, plan=None):
    # Streaming grid (bh, k-tile, q-tile): k_ref [1, BK, D] and v_ref
    # [1, BK, Dv] fixed per (bh, kk); q_ref [1, BQ, D] and g_ref [1, BQ, Dv]
    # = this step's q tile; lse_ref/delta_ref [1, 1, BQ] tiles; optional
    # glse [1, 1, BQ]; outputs dk [1, BK, D] and dv [1, BK, Dv] written at
    # the last q step; scratch dk/dv accumulators persist across the q
    # dimension.
    qseg_ref, kseg_ref, seed_ref, offs_ref, outs = _unpack_rest(
        rest, has_seg, dropout_rate, has_offsets)
    if with_lse:
        glse_ref, dk_ref, dv_ref, dk_s, dv_s = outs
    else:
        glse_ref = None
        dk_ref, dv_ref, dk_s, dv_s = outs

    bk = k_ref.shape[1]
    bq = q_ref.shape[1]
    qq = pl.program_id(2)
    n_q = pl.num_programs(2)
    k_off = pl.program_id(1) * bk
    # banded: step qq holds the qq-th q tile of this k tile's band
    q_tile = _first_q_tile(pl.program_id(1), bq, bk) + qq if banded else qq
    q_off = q_tile * bq
    bh_idx = pl.program_id(0)
    seed = seed_ref[0, 0].astype(jnp.uint32) if seed_ref is not None else None
    goff_q = offs_ref[0, 0] if has_offsets else 0
    goff_k = offs_ref[0, 1] if has_offsets else 0

    @pl.when(qq == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def accumulate(cols, rows, mask, keep_inv):
        """Add the pairs of query rows ``rows`` and key rows ``cols``."""
        q = q_ref[0, rows, :]
        g = g_ref[0, rows, :]
        a_drop, ds = _score_grads(
            q, g, k_ref[0, cols, :], v_ref[0, cols, :], lse_ref[0, 0, rows],
            delta_ref[0, 0, rows], glse_ref[0, 0, rows] if with_lse else None,
            sm_scale, mask, keep_inv)
        dv_s[cols] = lax.add(dv_s[cols], lax.dot_general(
            lax.convert_element_type(a_drop, g.dtype), g,
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32))
        dk_s[cols] = lax.add(dk_s[cols], lax.dot_general(
            lax.convert_element_type(ds, q.dtype), q,
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32))

    def _tile(masked):
        mask, keep_inv = _generic_masks(
            masked, causal, window, has_seg, dropout_rate, qseg_ref, kseg_ref,
            seed, bh_idx, goff_q + q_off, bq, goff_k + k_off, bk)
        accumulate(slice(None), slice(None), mask, keep_inv)

    def _edge(strips):
        # each sub-tile column takes the pieces of its strip of query rows
        # that hold a visible pair; only a piece an edge crosses is masked
        gap = _sub_gap(plan.sub)
        for c, pieces in enumerate(strips):
            cols = slice(c * plan.sub, (c + 1) * plan.sub)
            for piece in pieces:
                accumulate(cols, slice(piece.start, piece.stop),
                           _piece_mask(piece, gap), None)

    # causal: this q tile contributes only if its last row sees the
    # k tile's first row (and, under a window, its first row the last)
    run = _tile_runs(causal, window, goff_q + q_off, bq, goff_k + k_off, bk,
                     qq >= 0)
    inside = q_tile < n_tiles if banded else True
    _when_classified(plan and (plan.interior, plan.cols), run, inside,
                     q_tile - pl.program_id(1), _tile, _edge)

    @pl.when(qq == n_q - 1)
    def _finish():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref, *rest,
               sm_scale, causal, has_seg, dropout_rate,
               has_offsets, with_lse, window=None, banded=False,
               n_tiles=None, plan=None):
    # Streaming grid (bh, q-tile, k-tile): q_ref [1, BQ, D] and g_ref
    # [1, BQ, Dv] fixed per (bh, j); k_ref [1, BK, D] and v_ref [1, BK, Dv]
    # = this step's tile; lse_ref/delta_ref [1, 1, BQ]; optional glse
    # [1, 1, BQ]; output dq [1, BQ, D] written at the last k step; scratch
    # dq accumulator.
    qseg_ref, kseg_ref, seed_ref, offs_ref, outs = _unpack_rest(
        rest, has_seg, dropout_rate, has_offsets)
    if with_lse:
        glse_ref, dq_ref, dq_s = outs
    else:
        glse_ref = None
        dq_ref, dq_s = outs

    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    kk = pl.program_id(2)
    n_k = pl.num_programs(2)
    q_off = pl.program_id(1) * bq
    # banded: step kk holds the kk-th k tile of this q tile's band
    k_tile = (_first_k_tile(pl.program_id(1), bq, bk, window) + kk
              if banded else kk)
    k_off = k_tile * bk
    bh_idx = pl.program_id(0)
    seed = seed_ref[0, 0].astype(jnp.uint32) if seed_ref is not None else None
    goff_q = offs_ref[0, 0] if has_offsets else 0
    goff_k = offs_ref[0, 1] if has_offsets else 0

    @pl.when(kk == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    def accumulate(rows, cols, mask, keep_inv):
        """Add the pairs of query rows ``rows`` and key rows ``cols``."""
        k = k_ref[0, cols, :]
        _, ds = _score_grads(
            q_ref[0, rows, :], g_ref[0, rows, :], k, v_ref[0, cols, :],
            lse_ref[0, 0, rows], delta_ref[0, 0, rows],
            glse_ref[0, 0, rows] if with_lse else None, sm_scale, mask,
            keep_inv)
        dq_s[rows] = lax.add(dq_s[rows], lax.dot_general(
            lax.convert_element_type(ds, k.dtype), k,
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32))

    def _tile(masked):
        mask, keep_inv = _generic_masks(
            masked, causal, window, has_seg, dropout_rate, qseg_ref, kseg_ref,
            seed, bh_idx, goff_q + q_off, bq, goff_k + k_off, bk)
        accumulate(slice(None), slice(None), mask, keep_inv)

    def _edge(strips):
        # each sub-tile row takes the pieces of its strip of key rows that
        # hold a visible pair; only a piece an edge crosses is masked
        gap = _sub_gap(plan.sub)
        for a, pieces in enumerate(strips):
            rows = slice(a * plan.sub, (a + 1) * plan.sub)
            for piece in pieces:
                accumulate(rows, slice(piece.start, piece.stop),
                           _piece_mask(piece, gap), None)

    run = _tile_runs(causal, window, goff_q + q_off, bq, goff_k + k_off, bk,
                     kk >= 0)
    # a band that runs off the end of the sequence holds no tile there
    inside = k_tile < n_tiles if banded else True
    _when_classified(plan and (plan.interior, plan.rows), run, inside,
                     pl.program_id(1) - k_tile, _tile, _edge)

    @pl.when(kk == n_k - 1)
    def _finish():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _pallas_backward(q, k, v, out, lse, qseg, kseg, seed, offs, g, g_lse,
                     causal, sm_scale, block_q, block_k, dropout_rate,
                     interpret, window=None):
    b, tq, h, d = q.shape
    tk, hk = k.shape[1], k.shape[2]
    d_v = v.shape[3]  # the head size of v, out and g; d is q's and k's
    grp = h // hk  # q heads per kv head (GQA); dk/dv computed per q head
    scale = sm_scale if sm_scale is not None else d ** -0.5
    bq = min(block_q, tq)
    bk = min(block_k, tk)
    qf, kf, vf, of, gf = _fold(q), _fold(k), _fold(v), _fold(out), _fold(g)
    kv_row = lambda i: (i // h) * hk + (i % h) // grp
    # delta = rowsum(dO * O): cheap fused elementwise+reduce, XLA's job.
    # lse arrives as [B*H, 1, T] (see _forward's tiling note); delta gets
    # the same singleton-row layout.
    delta = (gf.astype(jnp.float32) * of.astype(jnp.float32)).sum(
        -1, keepdims=True).swapaxes(1, 2)
    has_seg = qseg is not None
    has_offsets = offs is not None
    with_lse = g_lse is not None
    kw = {} if _VMEM is None else {"memory_space": _VMEM}
    shape = lambda s, dt: _shape_like(qf, s, dt)
    seed_in = ([] if dropout_rate == 0.0 else [seed.reshape(1, 1)])
    seed_spec = ([] if dropout_rate == 0.0 else
                 [pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0), **kw)])
    offs_in = ([offs] if has_offsets else [])
    offs_spec = ([pl.BlockSpec((1, 2), lambda i, j, kk: (i // h, 0), **kw)]
                 if has_offsets else [])
    window = _effective_window(window, has_offsets, tq)
    plan = _tile_plan(causal, window, has_offsets, has_seg, dropout_rate,
                      tq, tk, bq, bk, _SUB)
    band = _band(window, has_offsets, bq, bk, tq // bq, tk // bk,
                 plan is not None)
    k_steps, k_of, q_steps, q_of = (band.k_steps, band.k_of, band.q_steps,
                                    band.q_of)

    # dk/dv: grid (bh, k-tile, q-tile) — q/g/lse/delta stream over the
    # minor q dimension; the k/v tile and the scratch accumulators are
    # fixed per (bh, k-tile)
    dkv_kern = functools.partial(
        _dkv_kernel, sm_scale=scale, causal=causal,
        has_seg=has_seg, dropout_rate=dropout_rate,
        has_offsets=has_offsets, with_lse=with_lse, plan=plan,
        **band.q_kwargs)
    q_tile = lambda width: pl.BlockSpec(
        (1, bq, width), lambda i, j, qq: (i, q_of(j, qq), 0), **kw)
    vec_q = lambda: pl.BlockSpec(
        (1, 1, bq), lambda i, j, qq: (i, 0, q_of(j, qq)), **kw)
    ins = [qf, gf, kf, vf, lse, delta]
    in_specs = [q_tile(d), q_tile(d_v),
                pl.BlockSpec((1, bk, d),
                             lambda i, j, qq: (kv_row(i), j, 0), **kw),
                pl.BlockSpec((1, bk, d_v),
                             lambda i, j, qq: (kv_row(i), j, 0), **kw),
                vec_q(), vec_q()]
    if has_seg:
        ins += [qseg.reshape(b, 1, tq), kseg.reshape(b, 1, tk)]
        in_specs += [
            pl.BlockSpec((1, 1, bq),
                         lambda i, j, qq: (i // h, 0, q_of(j, qq)), **kw),
            pl.BlockSpec((1, 1, bk), lambda i, j, qq: (i // h, 0, j), **kw)]
    ins += seed_in
    in_specs += seed_spec
    ins += offs_in
    in_specs += offs_spec
    if with_lse:
        ins.append(g_lse)
        in_specs.append(vec_q())
    dk, dv = pl.pallas_call(
        dkv_kern,
        grid=(b * h, tk // bk, q_steps),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda i, j, qq: (i, j, 0), **kw),
            pl.BlockSpec((1, bk, d_v), lambda i, j, qq: (i, j, 0), **kw)],
        out_shape=[shape((b * h, tk, d), k.dtype),
                   shape((b * h, tk, d_v), v.dtype)],
        scratch_shapes=_scratch([((bk, d), jnp.float32),
                                 ((bk, d_v), jnp.float32)]),
        interpret=interpret,
    )(*ins)
    if grp > 1:
        # each kv head's gradient is the sum over its q-head group —
        # accumulated in f32 (the kernel's partials were cast to the
        # output dtype; summing them in bf16 would compound rounding the
        # blockwise oracle doesn't have)
        group_sum = lambda x: x.astype(jnp.float32).reshape(
            b, hk, grp, tk, x.shape[-1]).sum(2).reshape(
                b * hk, tk, x.shape[-1]).astype(x.dtype)
        dk, dv = group_sum(dk), group_sum(dv)

    # dq: grid (bh, q-tile, k-tile) — k/v stream over the minor k
    # dimension; the q/g/lse/delta tiles and the dq scratch are fixed
    dq_kern = functools.partial(
        _dq_kernel, sm_scale=scale, causal=causal,
        has_seg=has_seg, dropout_rate=dropout_rate,
        has_offsets=has_offsets, with_lse=with_lse, plan=plan,
        **band.k_kwargs)
    vec_j = lambda: pl.BlockSpec((1, 1, bq), lambda i, j, kk: (i, 0, j),
                                 **kw)
    ins = [qf, gf, kf, vf, lse, delta]
    in_specs = [pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, 0), **kw),
                pl.BlockSpec((1, bq, d_v), lambda i, j, kk: (i, j, 0), **kw),
                pl.BlockSpec((1, bk, d),
                             lambda i, j, kk: (kv_row(i), k_of(j, kk), 0),
                             **kw),
                pl.BlockSpec((1, bk, d_v),
                             lambda i, j, kk: (kv_row(i), k_of(j, kk), 0),
                             **kw),
                vec_j(), vec_j()]
    if has_seg:
        ins += [qseg.reshape(b, 1, tq), kseg.reshape(b, 1, tk)]
        in_specs += [
            pl.BlockSpec((1, 1, bq), lambda i, j, kk: (i // h, 0, j), **kw),
            pl.BlockSpec((1, 1, bk),
                         lambda i, j, kk: (i // h, 0, k_of(j, kk)), **kw)]
    ins += seed_in
    in_specs += seed_spec
    ins += offs_in
    in_specs += offs_spec
    if with_lse:
        ins.append(g_lse)
        in_specs.append(vec_j())
    dq = pl.pallas_call(
        dq_kern,
        grid=(b * h, tq // bq, k_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, 0), **kw),
        out_shape=shape((b * h, tq, d), q.dtype),
        scratch_shapes=_scratch([((bq, d), jnp.float32)]),
        interpret=interpret,
    )(*ins)

    unfold = lambda x, t_, h_: x.reshape(
        b, h_, t_, x.shape[-1]).transpose(0, 2, 1, 3)
    return unfold(dq, tq, h), unfold(dk, tk, hk), unfold(dv, tk, hk)


def _blockwise_backward(q, k, v, out, lse, qseg, kseg, seed, offs, g, g_lse,
                        causal, sm_scale, block_k, dropout_rate, window=None):
    """Pure-XLA blockwise flash backward — the gradient-parity oracle.

    Identical math to the Pallas kernels (saved-lse softmax, the same
    hash-based dropout mask), expressed as a `lax.scan` over K/V tiles so
    the [T, T] matrix is still never materialized.
    """
    b, tq, h, d = q.shape
    tk, hk = k.shape[1], k.shape[2]
    grp = h // hk
    scale = sm_scale if sm_scale is not None else d ** -0.5
    bk = min(block_k, tk)
    n = tk // bk
    # [B, T, H, D] -> [B, H, T, D] f32 working layout
    tr = lambda x: x.transpose(0, 2, 1, 3).astype(jnp.float32)
    qT, oT, gT = tr(q), tr(out), tr(g)
    kT, vT = tr(k), tr(v)
    if grp > 1:  # GQA: expand kv to one head per q head for the math
        rep = lambda x: jnp.repeat(x, grp, axis=1)
        kT, vT = rep(kT), rep(vT)
    lseT = lse.reshape(b, h, tq)  # lse arrives [B*H, 1, Tq]
    glseT = g_lse.reshape(b, h, tq) if g_lse is not None else None
    # offs is [B, 2] (per-sequence offsets); broadcast as [B, 1, T|S, 1]
    # planes so the mask/dropout math matches the per-program scalars the
    # Pallas kernels read
    if offs is not None:
        goff_q = offs[:, 0].reshape(b, 1, 1, 1)
        goff_k = offs[:, 1].reshape(b, 1, 1, 1)
    else:
        goff_q = goff_k = jnp.zeros((1, 1, 1, 1), jnp.int32)
    q_pos = goff_q + jnp.arange(tq).reshape(1, 1, tq, 1)   # [B|1,1,T,1]
    bh_idx = jnp.arange(b * h).reshape(b, h, 1, 1)
    D = (gT * oT).sum(-1)                                  # [B, H, T]
    inv = 1.0 / (1.0 - dropout_rate) if dropout_rate > 0.0 else 1.0

    def k_pos_tile(j):
        return goff_k + (j * bk + jnp.arange(bk)).reshape(1, 1, 1, bk)

    def tile_mask(j):
        mask = None
        if causal:
            mask = q_pos >= k_pos_tile(j)                  # [B|1,1,T,S]
            if window is not None:
                mask = mask & (q_pos - k_pos_tile(j) < window)
        if qseg is not None:
            kseg_j = jax.lax.dynamic_slice_in_dim(kseg, j * bk, bk, axis=1)
            m2 = (qseg[:, None, :, None] == kseg_j[:, None, None, :])
            mask = m2 if mask is None else (mask & m2)
        return mask

    def keep(j):
        if dropout_rate == 0.0:
            return None
        return _keep_mask(seed.astype(jnp.uint32), bh_idx,
                          q_pos, k_pos_tile(j), dropout_rate)

    def grad_fold(dq, j):
        kb = jax.lax.dynamic_slice_in_dim(kT, j * bk, bk, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(vT, j * bk, bk, axis=2)
        s = jnp.einsum("bhtd,bhsd->bhts", qT, kb,
                       preferred_element_type=jnp.float32) * scale
        a = jnp.exp(s - lseT[..., None])
        mask = tile_mask(j)
        if mask is not None:
            a = jnp.where(mask, a, 0.0)
        dp = jnp.einsum("bhtd,bhsd->bhts", gT, vb)
        km = keep(j)
        if km is not None:
            a_drop = jnp.where(km, a * inv, 0.0)
            da = jnp.where(km, dp * inv, 0.0)
        else:
            a_drop = a
            da = dp
        dv_j = jnp.einsum("bhts,bhtd->bhsd", a_drop, gT)
        ds = a * (da - D[..., None]) * scale
        if glseT is not None:
            ds = ds + a * glseT[..., None] * scale
        dq = dq + jnp.einsum("bhts,bhsd->bhtd", ds, kb)
        dk_j = jnp.einsum("bhts,bhtd->bhsd", ds, qT)
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros_like(qT)
    dq, (dk_tiles, dv_tiles) = jax.lax.scan(grad_fold, dq0, jnp.arange(n))
    # [n, B, H, bk, D] -> [B, H, Tk, D]
    merge = lambda tiles: tiles.transpose(1, 2, 0, 3, 4).reshape(
        b, h, tk, tiles.shape[-1])
    back = lambda x, ref: x.transpose(0, 2, 1, 3).astype(ref.dtype)
    dk_full, dv_full = merge(dk_tiles), merge(dv_tiles)
    if grp > 1:  # sum each kv head's gradient over its q-head group
        gsum = lambda x: x.reshape(b, hk, grp, tk, x.shape[-1]).sum(2)
        dk_full, dv_full = gsum(dk_full), gsum(dv_full)
    return (back(dq, q), back(dk_full, k), back(dv_full, v))


# ---------------------------------------------------------------------------
# custom_vjp plumbing + public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(7, 8, 9, 10, 11, 12, 13, 14))
def _flash(q, k, v, qseg, kseg, seed, offs, dropout_rate, causal, sm_scale,
           block_q, block_k, bwd_impl, with_lse, window):
    interpret = jax.default_backend() != "tpu"
    out, lse = _forward(q, k, v, qseg, kseg, seed, offs, causal, sm_scale,
                        block_q, block_k, dropout_rate, interpret, window)
    if with_lse:
        b, t, h, _ = q.shape
        return out, lse.reshape(b, h, t)
    return out


def _flash_fwd(q, k, v, qseg, kseg, seed, offs, dropout_rate, causal,
               sm_scale, block_q, block_k, bwd_impl, with_lse, window):
    interpret = jax.default_backend() != "tpu"
    out, lse = _forward(q, k, v, qseg, kseg, seed, offs, causal, sm_scale,
                        block_q, block_k, dropout_rate, interpret, window)
    res = (q, k, v, out, lse, qseg, kseg, seed, offs)
    if with_lse:
        b, t, h, _ = q.shape
        return (out, lse.reshape(b, h, t)), res
    return out, res


def _flash_bwd(dropout_rate, causal, sm_scale, block_q, block_k, bwd_impl,
               with_lse, window, res, g):
    q, k, v, out, lse, qseg, kseg, seed, offs = res
    if with_lse:
        g, g_lse_bht = g
        b, t, h, _ = q.shape
        g_lse = g_lse_bht.reshape(b * h, 1, t).astype(jnp.float32)
    else:
        g_lse = None
    if bwd_impl == "pallas":
        interpret = jax.default_backend() != "tpu"
        dq, dk, dv = _pallas_backward(
            q, k, v, out, lse, qseg, kseg, seed, offs, g, g_lse, causal,
            sm_scale, block_q, block_k, dropout_rate, interpret, window)
    elif bwd_impl == "blockwise":
        dq, dk, dv = _blockwise_backward(
            q, k, v, out, lse, qseg, kseg, seed, offs, g, g_lse, causal,
            sm_scale, block_k, dropout_rate, window)
    else:
        raise ValueError(f"unknown bwd_impl {bwd_impl!r} "
                         "(expected 'pallas' or 'blockwise')")
    return dq, dk, dv, None, None, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def _fit_block(t: int, requested: Optional[int], default: int) -> int:
    """Resolve a block size.  Explicit sizes are strict (must divide T, as
    before); the default auto-shrinks by halving until it divides — so the
    larger shipped default never rejects a T an older default accepted."""
    if requested is not None:
        b = min(int(requested), t)
        if t % b:
            raise ValueError(
                f"flash_attention needs seq len ({t}) divisible by its "
                f"tiles ({b}); pad the sequence or pass smaller block "
                f"sizes")
        return b
    b = min(default, t)
    while b > 1 and t % b:
        b //= 2
    if t % b:
        raise ValueError(
            f"flash_attention cannot tile seq len {t}; pass block_q/"
            f"block_k that divide it (or pad the sequence)")
    return b


def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    *, q_segment_ids=None, kv_segment_ids=None,
                    dropout_rate: float = 0.0, dropout_seed=None,
                    q_offset=None, kv_offset=None,
                    return_lse: bool = False,
                    bwd_impl: str = "pallas",
                    window: Optional[int] = None):
    """Fused softmax attention: q [B, Tq, H, D], k [B, Tkv, Hkv, D],
    v [B, Tkv, Hkv, Dv] -> [B, Tq, H, Dv].  What must agree: the batch of
    all three, the kv length and kv heads of k and v, and the head size of
    q and k; the VALUE head size ``Dv`` is v's own (latent attention scores
    192-wide keys and sums 128-wide values) and is neither padded nor
    copied: v, the output and their gradients move at ``Dv``, q, k and
    theirs at ``D``, and the default ``sm_scale`` is ``D ** -0.5``.
    ``Tq != Tkv`` is supported (cross-attention /
    decode-over-cache); with ``causal`` the mask compares GLOBAL
    positions (row ``q_offset+i`` sees column ``kv_offset+j`` iff
    ``i+q_offset >= j+kv_offset``).  ``Hkv`` may divide ``H``
    (grouped-query / multi-query attention): each kv head serves
    ``H/Hkv`` q heads — the kernels read the shared K/V tiles via index
    maps (no materialized repeat) and dk/dv sum over each group.

    Drop-in for :func:`chainermn_tpu.parallel.sequence.attention` (same
    signature minus offsets); pass as ``attn_fn=`` to
    ``ulysses_attention`` for a fused inner kernel.  ``block_q``/
    ``block_k`` tune the tile sizes: explicit values must divide the
    sequence length (or cover it in one tile); the default is
    dtype-aware (1024 for sub-4-byte q/k/v — the measured v5e optimum —
    and 512 when any operand is f32, whose tiles would overflow the
    backward's VMEM budget at 1024) and auto-halves until it divides,
    so any T a smaller default accepted still works.

    Extra keyword-only features:

    * ``q_segment_ids`` / ``kv_segment_ids`` — [B, T] int32 ids;
      position pairs attend only when ids match (packed sequences,
      padding).  Passing either defaults the other to zeros.
    * ``dropout_rate`` + ``dropout_seed`` — attention dropout; the seed
      is a traced uint32 scalar (vary it per training step).
    * ``q_offset`` / ``kv_offset`` — global positions of the first local
      row: traced int scalars (shared by the batch — ring attention's
      blocks of a longer sequence) or ``[B]`` int32 vectors giving every
      sequence its own offset (decode over a paged KV cache, where each
      batch row sits at a different cache length).  The causal mask and
      the dropout hash both use global positions.
    * ``return_lse`` — also return the per-row logsumexp [B, H, T]
      (float32; fully-masked rows hold the sentinel 1e30).  The lse is
      DIFFERENTIABLE: its cotangent adds ``a_ij * g_lse_i`` to the score
      gradients in both backward implementations, which is what lets
      downstream logsumexp merges (ring attention) backprop exactly.
    * ``bwd_impl`` — "pallas" (default, fused backward kernels) or
      "blockwise" (pure-XLA oracle with identical math).
    * ``window`` — with ``causal``, a sliding window over global positions:
      row i sees column j only where ``0 <= i - j < window``.  All three
      kernels skip a tile that holds no such pair, on both sides of the
      band, and without offsets do not fetch it either.  ``None`` (the
      default) is plain causal attention, and so is, without offsets, a
      window as long as the queries.
    """
    if window is not None:
        if not causal:
            raise ValueError("window needs causal=True: it bounds how far "
                             "back a row sees")
        window = int(window)
        if window < 1:
            raise ValueError(f"window must be at least 1, got {window}")
    if (q_segment_ids is not None) or (kv_segment_ids is not None):
        if q_segment_ids is None:
            q_segment_ids = jnp.zeros(q.shape[:2], jnp.int32)
        if kv_segment_ids is None:
            kv_segment_ids = jnp.zeros(k.shape[:2], jnp.int32)
        q_segment_ids = q_segment_ids.astype(jnp.int32)
        kv_segment_ids = kv_segment_ids.astype(jnp.int32)
    dropout_rate = float(dropout_rate)
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got {dropout_rate}")
    if dropout_rate > 0.0:
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")
        dropout_seed = jnp.asarray(dropout_seed, jnp.uint32)
    else:
        dropout_seed = None
    if (q_offset is not None) or (kv_offset is not None):
        # offsets ride as one [B, 2] int32 array: column 0 = q, column 1 =
        # kv.  Scalars broadcast over the batch (ring attention's shared
        # block offsets); [B] arrays give every sequence its own global
        # position — decode-over-a-paged-cache, where each row of the
        # batch sits at a different cache length.
        b = q.shape[0]

        def _off_vec(o, label):
            o = jnp.asarray(0 if o is None else o, jnp.int32)
            if o.ndim == 0:
                return jnp.broadcast_to(o, (b,))
            if o.shape != (b,):
                raise ValueError(
                    f"{label} must be a scalar or a [batch] vector; got "
                    f"shape {o.shape} for batch {b}")
            return o

        offs = jnp.stack([_off_vec(q_offset, "q_offset"),
                          _off_vec(kv_offset, "kv_offset")], axis=1)
    else:
        offs = None
    # cross-attention supported: Tq (from q) and Tkv (from k/v) may
    # differ; GQA/MQA supported: k/v head count may divide q's
    if k.shape[:3] != v.shape[:3]:
        raise ValueError(
            "k and v must share batch, kv length and kv heads (the head "
            f"size of v is its own): {k.shape} vs {v.shape}")
    if (q.shape[0], q.shape[3]) != (k.shape[0], k.shape[3]):
        raise ValueError(
            "q and k must share batch and head size (v's head size is its "
            f"own, the output's): {q.shape} vs {k.shape}")
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"q head count ({q.shape[2]}) must be a multiple of the kv "
            f"head count ({k.shape[2]}) for grouped-query attention")
    # default blocks are dtype-aware: 1024x1024 is the measured bf16
    # optimum, but f32 tiles double every VMEM buffer and the backward's
    # scoped allocation overflows the 16 MB budget — 512 fits with room
    # (widest of q/k/v decides: any f32 operand inflates the tiles)
    if max(jnp.dtype(a.dtype).itemsize for a in (q, k, v)) >= 4:
        dq_def, dk_def = min(_BLOCK_Q, 512), min(_BLOCK_K, 512)
    else:
        dq_def, dk_def = _BLOCK_Q, _BLOCK_K
    bq = _fit_block(q.shape[1], block_q, dq_def)
    bk = _fit_block(k.shape[1], block_k, dk_def)
    return _flash(q, k, v, q_segment_ids, kv_segment_ids, dropout_seed,
                  offs, dropout_rate, bool(causal), sm_scale, bq, bk,
                  bwd_impl, bool(return_lse), window)


def flash_tile_census(tq: int, tk: int, block_q: Optional[int] = None,
                      block_k: Optional[int] = None,
                      window: Optional[int] = None,
                      sub: Optional[int] = None, *, causal: bool = True,
                      offsets: bool = False, segment_ids: bool = False,
                      dropout_rate: float = 0.0,
                      head_dim: Optional[int] = None,
                      value_head_dim: Optional[int] = None) -> dict:
    """How much of a call's work the tile classes take away: a counter of
    shapes alone, from the arithmetic the kernels use (:func:`_tile_plan`,
    :func:`_visible`), for one (batch, head) of one kernel.

    ``classified``: whether the kernels know a tile's class (causal, no
    offsets, no segment ids, no dropout, both sides tiled alike; see
    :func:`_tile_plan`).  ``visited`` tiles hold a visible pair and do work:
    ``interior`` (every pair visible) + ``edge`` (the diagonal or a window's
    far edge crosses it); ``outside`` tiles of the plane hold none.
    ``subtiles_total`` counts the visited tiles' sub-tiles of ``sub`` x
    ``sub`` pairs (the file's ``_SUB`` unless given; the whole tile where it
    does not divide the tile: ``sub`` is the query side's), ``subtiles_run`` those that do work: all of
    them where ``classified`` is false, else the interior tiles' and the edge
    tiles' sub-tiles that hold a visible pair.  ``visible_pair_share`` is the
    visible pairs over the pairs of the sub-tiles run (1.0: no score is
    computed that the mask throws away).  Blocks default as
    :func:`flash_attention`'s do for operands under four bytes.

    With ``head_dim`` (q's and k's; ``value_head_dim`` is v's own and
    defaults to it) also the forward kernel's matmul operations for that
    (batch, head), a multiply-add as two: ``forward_flop_run`` over the pairs
    of the sub-tiles run (scores at ``head_dim``, the weighted sum at
    ``value_head_dim``) and ``forward_flop_visible`` over the visible pairs
    alone; the two backward kernels run twice that and recompute the
    scores."""
    bq = _fit_block(tq, block_q, _BLOCK_Q)
    bk = _fit_block(tk, block_k, _BLOCK_K)
    if window is not None and not causal:
        raise ValueError("window needs causal=True")
    window = _effective_window(window, offsets, tq)
    sub = _SUB if sub is None else int(sub)
    plan = _tile_plan(causal, window, offsets, segment_ids, dropout_rate,
                      tq, tk, bq, bk, sub)
    counts = {"interior": 0, "edge": 0, "outside": 0}
    for j in range(tq // bq):
        for kt in range(tk // bk):
            seen = (_visible(j * bq - (kt * bk + bk - 1),
                             j * bq + bq - 1 - kt * bk, window)
                    if causal else (False, False))
            counts["outside" if seen is None else
                   "interior" if seen == (False, False) else "edge"] += 1
    visited = counts["interior"] + counts["edge"]
    per_tile = (bq // _sub_of(bq, sub)) * (bk // _sub_of(bk, sub))
    sub = _sub_of(bq, sub)
    if plan is None:
        subtiles_run = visited * per_tile
    else:
        n = tq // bq
        subtiles_run = counts["interior"] * per_tile + sum(
            (n - rel) * sum((p.stop - p.start) // sub
                            for pieces in strips for p in pieces)
            for rel, strips in plan.rows.items())
    if causal:
        reach = tk if window is None else window
        visible = sum(max(min(i, tk - 1) - max(i - reach + 1, 0) + 1, 0)
                      for i in range(tq))
    else:
        visible = tq * tk
    run_pairs = subtiles_run * (bq * bk // per_tile)
    census = {"classified": plan is not None, "sub": sub, "visited": visited,
              **counts, "subtiles_run": subtiles_run,
              "subtiles_total": visited * per_tile,
              "visible_pair_share": visible / run_pairs if run_pairs else 0.0}
    if head_dim is not None:
        per_pair = 2 * (int(head_dim) + int(value_head_dim or head_dim))
        census["forward_flop_run"] = run_pairs * per_pair
        census["forward_flop_visible"] = visible * per_pair
    return census


__all__ = ["flash_attention", "flash_tile_census"]
