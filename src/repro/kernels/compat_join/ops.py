"""Public jit'd wrappers for the compat_join Pallas kernels.

Responsibilities:

* **Spec normalization cache** — ``normalize_spec`` converts the
  REL/TREL numpy matrices into hashable nested tuples ONCE per distinct
  spec (lru-cached by content), so repeated joins with the same spec
  reuse the *identical* static kernel key instead of rebuilding nested
  tuples per tick.
* **Packing, tiling + padding** — each side's (bind, ets, valid) is
  packed into one int32 operand of ``[K, tile]`` tiles (table rows on
  lanes; see ``kernel``), tile sizes come from ``kernel.choose_tiles``
  (shape-derived), and the capacity axes are padded to tile multiples
  with ``valid=0`` rows that never match.
* **Live extents** — for ``compat_join_pairs``, each side's extent per
  slot (the tiles up to and including the last tile with a valid row)
  is reduced from the packed operand's validity row and passed to the
  kernel as scalar prefetch, so the kernel sweeps the live tiles and not
  the capacity; a side shared by every slot has one extent, broadcast.
  Whether a side is one whole-axis VMEM block or stays on the grid is
  chosen from its shape (``kernel.sweep_blocks``).
* **Batched (vmapped) dispatch** — each op is wrapped in
  ``jax.custom_batching.custom_vmap``: an unvmapped call is the
  one-slot case of the kernel's ``(slot, A-block, B-block)`` grid, while
  a vmapped call (the slot ticks of ``repro.core.multi``) lowers to ONE
  such kernel for the whole slot group — one ``pallas_call`` per join,
  with per-slot traced windows.  A side shared across slots (e.g. the
  slot tick's stream edges in a prefix-node join) is NOT broadcast: it
  has no slot axis and the kernel's index_map ignores the slot grid dim.
* **Traced window** — ``window`` is passed to the kernel as a
  scalar-prefetch input; changing it (or any slot's window) never
  recompiles.  Only *whether* a window predicate exists is static.

Ops:

``compat_mask``        -> bool [CA, CB]   (drop-in for
                          ``core.join.compat_mask_ref``)
``compat_join_pairs``  -> (a_idx, b_idx, pair_valid, n_dropped), the
                          fused equivalent of ``compat_mask`` +
                          ``core.join.extract_pairs`` with no [CA, CB]
                          mask materialized in HBM.  Pairs are emitted
                          in tile order: same pair SET and exact
                          ``n_dropped``; under overflow the keep-subset
                          is the tile-order prefix (``extract_pairs``
                          keeps a row-major one).
"""

from __future__ import annotations

import functools

import numpy as np
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.kernels.compat_join import kernel as K


# --------------------------------------------------------------------- #
# Spec normalization (lru-cached by content).
# --------------------------------------------------------------------- #
@functools.lru_cache(maxsize=1024)
def _spec_from_bytes(rel_bytes, rel_shape, trel_bytes, trel_shape):
    rel = np.frombuffer(rel_bytes, dtype=np.bool_).reshape(rel_shape)
    trel = np.frombuffer(trel_bytes, dtype=np.int8).reshape(trel_shape)
    return (tuple(map(tuple, rel.tolist())),
            tuple(map(tuple, trel.tolist())))


def normalize_spec(rel, trel):
    """Hashable nested-tuple ``(rel, trel)`` static kernel key.

    Cached by content so every tick that joins with the same spec gets
    back the *same* tuple objects — hash once, compare by identity —
    instead of rebuilding ``tuple(map(tuple, rel.tolist()))`` per call.
    """
    rel = np.ascontiguousarray(np.asarray(rel, dtype=np.bool_))
    trel = np.ascontiguousarray(np.asarray(trel, dtype=np.int8))
    return _spec_from_bytes(rel.tobytes(), rel.shape,
                            trel.tobytes(), trel.shape)


# --------------------------------------------------------------------- #
# Packing + padding.
# --------------------------------------------------------------------- #
_ceil_to = K._ceil_to
_UNBATCHED = (False,) * 7       # in_batched of an unvmapped call


def _pad_to(x, n, axis):
    pad = n - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _as_window(window):
    """Traced 0-d int32 window (0 dummy when the predicate is off)."""
    if window is None:
        return jnp.zeros((), jnp.int32)
    return jnp.asarray(window, jnp.int32).reshape(())


def _pack_side(bind, ets, valid, flags, n_slots, tile):
    """One packed int32 operand ``[S?, n_tiles, nv + ne + 1, tile]``,
    padded with ``valid = 0`` rows: table rows on lanes within a tile,
    the validity flag the last row.

    The side carries the slot axis if any of its three parts does; the
    parts that don't are broadcast to it (only the narrow columns of a
    per-slot side, never a whole shared table).
    """
    parts = [bind, ets, valid[..., None]]
    batched = any(flags)
    if batched:
        parts = [x if f else jnp.broadcast_to(x, (n_slots,) + x.shape)
                 for x, f in zip(parts, flags)]
    packed = jnp.concatenate([x.astype(jnp.int32) for x in parts], axis=-1)
    c, k = packed.shape[-2:]
    packed = _pad_to(packed, _ceil_to(max(c, 1), tile), axis=-2)
    tiles = packed.reshape(packed.shape[:-2] + (-1, tile, k))
    return jnp.swapaxes(tiles, -1, -2), batched


def _extent(side, n_slots):
    """int32 ``[n_slots]``: tiles up to and including the last tile with
    a valid row (0 if none), from the validity row of a packed side."""
    live = jnp.any(side[..., -1, :] > 0, axis=-1)          # [S?, n_tiles]
    pos = jnp.arange(1, live.shape[-1] + 1, dtype=jnp.int32)
    ext = jnp.max(jnp.where(live, pos, 0), axis=-1)
    return jnp.broadcast_to(ext, (n_slots,))


def _prep(args, in_batched, n_slots, mask):
    """Window + packed operands + static kernel kwargs for one call.

    An unbatched call is the ``n_slots = 1`` stacked call with both
    sides shared.
    """
    bind_a, ets_a, valid_a, bind_b, ets_b, valid_b, window = args
    ca, cb = bind_a.shape[-2], bind_b.shape[-2]
    ta, tb = K.choose_tiles(ca, cb)
    if mask:
        ta = K.mask_tile_a(ta)
    a, a_batched = _pack_side(bind_a, ets_a, valid_a, in_batched[0:3],
                              n_slots, ta)
    b, b_batched = _pack_side(bind_b, ets_b, valid_b, in_batched[3:6],
                              n_slots, tb)
    window = jnp.broadcast_to(window, (n_slots,))
    kw = dict(widths=(bind_a.shape[-1], ets_a.shape[-1],
                      bind_b.shape[-1], ets_b.shape[-1]),
              tile_a=ta, tile_b=tb, n_slots=n_slots,
              a_batched=a_batched, b_batched=b_batched)
    return window, a, b, kw, ca, cb


# --------------------------------------------------------------------- #
# compat_mask: custom-vmap op per static (spec, has_window, interpret).
# --------------------------------------------------------------------- #
@functools.lru_cache(maxsize=None)
def _mask_op(rel, trel, has_window, interpret):
    def run(args, in_batched, n_slots):
        window, a, b, kw, ca, cb = _prep(args, in_batched, n_slots, True)
        out = K.compat_mask_kernel(
            window, a, b, rel=rel, trel=trel, has_window=has_window,
            interpret=interpret, **kw)
        return out[:, :ca, :cb].astype(jnp.bool_)

    @custom_vmap
    def op(*args):
        return run(args, _UNBATCHED, 1)[0]

    @op.def_vmap
    def _rule(axis_size, in_batched, *args):
        return run(args, in_batched, axis_size), True

    return op


def compat_mask(bind_a, ets_a, valid_a, bind_b, ets_b, valid_b, rel, trel,
                window=None, interpret: bool = False):
    """Drop-in replacement for ``core.join.compat_mask_ref`` -> bool [CA, CB].

    ``window`` may be a Python int or a traced scalar (per-slot runtime
    windows); it is a scalar-prefetch kernel input, not a compile-time
    constant.  Under ``jax.vmap`` the op lowers to one stacked
    3-D-grid kernel for the whole batch.
    """
    rel_tt, trel_tt = normalize_spec(rel, trel)
    op = _mask_op(rel_tt, trel_tt, window is not None, bool(interpret))
    return op(bind_a, ets_a, valid_a, bind_b, ets_b, valid_b,
              _as_window(window))


# --------------------------------------------------------------------- #
# compat_join_pairs: fused mask + on-chip pair extraction.
# --------------------------------------------------------------------- #
@functools.lru_cache(maxsize=None)
def _pairs_op(rel, trel, max_new, has_window, interpret):
    def run(args, in_batched, n_slots):
        window, a, b, kw, _, _ = _prep(args, in_batched, n_slots, False)
        blk_a, blk_b = K.sweep_blocks(a.shape, b.shape)
        a_idx, b_idx, n_total = K.compat_join_pairs_kernel(
            window, _extent(a, n_slots), _extent(b, n_slots), a, b,
            rel=rel, trel=trel, has_window=has_window, blk_a=blk_a,
            blk_b=blk_b, max_new=max_new, interpret=interpret, **kw)
        return (a_idx.reshape(n_slots, -1)[:, :max_new],
                b_idx.reshape(n_slots, -1)[:, :max_new],
                n_total[:, 0, 0])

    @custom_vmap
    def op(*args):
        return tuple(x[0] for x in run(args, _UNBATCHED, 1))

    @op.def_vmap
    def _rule(axis_size, in_batched, *args):
        return run(args, in_batched, axis_size), (True, True, True)

    return op


def compat_join_pairs(bind_a, ets_a, valid_a, bind_b, ets_b, valid_b,
                      rel, trel, max_new: int, window=None,
                      interpret: bool = False):
    """Fused ``compat_mask`` + ``extract_pairs``: top-``max_new``
    (a, b) pairs of the join, computed on-chip with no [CA, CB] mask
    ever written to HBM.

    Returns ``(a_idx, b_idx, pair_valid, n_dropped)`` with the same
    contract as ``core.join.extract_pairs`` applied to the mask, except
    that pairs are emitted in tile order, A-tile major (set semantics;
    ``n_dropped`` is exact, and under overflow the first ``max_new`` in
    tile order are kept).  Only the tiles below each side's live extent
    are swept; the rest hold no match, so the output is that of a sweep
    over every tile.
    """
    rel_tt, trel_tt = normalize_spec(rel, trel)
    op = _pairs_op(rel_tt, trel_tt, int(max_new), window is not None,
                   bool(interpret))
    a_raw, b_raw, n_total = op(
        bind_a, ets_a, valid_a, bind_b, ets_b, valid_b, _as_window(window))
    pair_valid = a_raw >= 0
    a_idx = jnp.maximum(a_raw, 0)
    b_idx = jnp.maximum(b_raw, 0)
    n_dropped = jnp.maximum(n_total - max_new, 0)
    return a_idx, b_idx, pair_valid, n_dropped
