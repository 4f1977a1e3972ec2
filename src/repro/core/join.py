"""The generic compatibility join (Definitions 7/8) and table append helpers.

``compat_mask`` is the computational hot spot of the whole system: every
incoming edge is joined against expansion-list items, and TC-subquery
deltas are joined against the global list.  The pure-jnp implementation
here is the reference; ``repro.kernels.compat_join`` provides the Pallas
TPU kernel with identical semantics (selected via ``JoinBackend``; the
platform chooses, see ``resolve_backend``).

Semantics of one (a, b) pair:
  * vertex slots:  rel[i, j]  => bind_a[a, i] == bind_b[b, j]
                   ~rel[i, j] => bind_a[a, i] != bind_b[b, j]   (injectivity)
  * edge slots:    trel[i, j] == -1 => ets_a[a, i] <  ets_b[b, j]
                   trel[i, j] == +1 => ets_a[a, i] >  ets_b[b, j]
  * both rows valid.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp


def compat_mask_ref(
    bind_a: jnp.ndarray,   # int32 [CA, NVA]
    ets_a: jnp.ndarray,    # int32 [CA, NEA]
    valid_a: jnp.ndarray,  # bool  [CA]
    bind_b: jnp.ndarray,   # int32 [CB, NVB]
    ets_b: jnp.ndarray,    # int32 [CB, NEB]
    valid_b: jnp.ndarray,  # bool  [CB]
    rel: np.ndarray,       # bool  [NVA, NVB]   (host constant)
    trel: np.ndarray,      # int8  [NEA, NEB]   (host constant)
    window: int | None = None,
) -> jnp.ndarray:          # bool [CA, CB]
    """Pure-jnp reference compatibility mask.

    Loops over the (tiny, static) slot-pair dimensions so no [CA, CB, NV]
    intermediate is ever materialized — each slot pair contributes one
    [CA, CB] comparison which XLA fuses.

    When ``window`` is given, adds the *window-span* predicate
    ``max(all ts) - min(all ts) < window``: the combined match must have
    been fully inside the sliding window at the moment its last edge
    arrived.  This is the dataflow image of the paper's §5.3 two-phase
    deletion — rows near expiry stay joinable for earlier-timestamped
    triggers and are invisible to later ones.
    """
    ca, cb = bind_a.shape[0], bind_b.shape[0]
    mask = valid_a[:, None] & valid_b[None, :]
    if window is not None:
        min_a = jnp.min(ets_a, axis=1)[:, None]
        max_a = jnp.max(ets_a, axis=1)[:, None]
        min_b = jnp.min(ets_b, axis=1)[None, :]
        max_b = jnp.max(ets_b, axis=1)[None, :]
        span = jnp.maximum(max_a, max_b) - jnp.minimum(min_a, min_b)
        mask = mask & (span < window)
    nva, nvb = rel.shape
    for i in range(nva):
        ai = bind_a[:, i][:, None]
        for j in range(nvb):
            bj = bind_b[:, j][None, :]
            if rel[i, j]:
                mask = mask & (ai == bj)
            else:
                mask = mask & (ai != bj)
    nea, neb = trel.shape
    for i in range(nea):
        ti = ets_a[:, i][:, None]
        for j in range(neb):
            if trel[i, j] == -1:
                mask = mask & (ti < ets_b[:, j][None, :])
            elif trel[i, j] == 1:
                mask = mask & (ti > ets_b[:, j][None, :])
    return mask


# --------------------------------------------------------------------- #
# Backend dispatch: pure-jnp reference vs Pallas kernel.
# --------------------------------------------------------------------- #
class JoinBackend:
    REF = "ref"
    PALLAS = "pallas"            # compiled TPU path
    PALLAS_INTERPRET = "pallas_interpret"  # kernel body interpreted on CPU


_BACKENDS = (JoinBackend.REF, JoinBackend.PALLAS, JoinBackend.PALLAS_INTERPRET)


def resolve_backend(backend: str | None = None) -> str:
    """The join backend to serve with: the platform picks it.

    ``None`` resolves to ``PALLAS`` (the compiled kernels) when the
    process's first device is a TPU and to ``REF`` otherwise.  An
    explicit ``REF`` / ``PALLAS_INTERPRET`` is honored (tests pin them);
    an explicit ``PALLAS`` off a TPU raises — the compiled kernels run
    nowhere else, and no path falls back silently.
    """
    platform = jax.devices()[0].platform
    if backend is None:
        return JoinBackend.PALLAS if platform == "tpu" else JoinBackend.REF
    if backend not in _BACKENDS:
        raise ValueError(f"unknown join backend: {backend!r}")
    if backend == JoinBackend.PALLAS and platform != "tpu":
        raise ValueError(
            f"join backend {backend!r} needs a TPU (platform is "
            f"{platform!r}); pass None to let the platform choose")
    return backend


def compat_mask(bind_a, ets_a, valid_a, bind_b, ets_b, valid_b, rel, trel,
                window: int | None = None,
                backend: str | None = None) -> jnp.ndarray:
    if backend is None:
        backend = resolve_backend()
    if backend == JoinBackend.REF:
        return compat_mask_ref(
            bind_a, ets_a, valid_a, bind_b, ets_b, valid_b, rel, trel, window)
    from repro.kernels.compat_join import ops as cj_ops
    return cj_ops.compat_mask(
        bind_a, ets_a, valid_a, bind_b, ets_b, valid_b, rel, trel, window,
        interpret=(backend == JoinBackend.PALLAS_INTERPRET))


def join_pairs(bind_a, ets_a, valid_a, bind_b, ets_b, valid_b, rel, trel,
               max_new: int, window: int | None = None,
               backend: str | None = None):
    """Fused compatibility join + pair extraction (the engine's hot path).

    Returns ``(a_idx, b_idx, pair_valid, n_dropped)`` — the contract of
    ``extract_pairs`` applied to the join mask.  Under the REF backend
    this *is* ``compat_mask_ref`` + ``extract_pairs`` (bit-identical to
    the historical two-step path).  Under the Pallas backends it lowers
    to the fused ``compat_join_pairs`` kernel, which extracts compacted
    pairs on-chip and never materializes the [CA, CB] mask in HBM; the
    kernel emits pairs in tile order, so cross-backend equality is on
    the pair SET (and the exact ``n_dropped``), with a backend-defined
    keep-subset in the overflow case.  ``backend=None`` resolves by
    platform (``resolve_backend``).
    """
    if backend is None:
        backend = resolve_backend()
    if backend == JoinBackend.REF:
        mask = compat_mask_ref(
            bind_a, ets_a, valid_a, bind_b, ets_b, valid_b, rel, trel, window)
        return extract_pairs(mask, max_new)
    from repro.kernels.compat_join import ops as cj_ops
    return cj_ops.compat_join_pairs(
        bind_a, ets_a, valid_a, bind_b, ets_b, valid_b, rel, trel,
        max_new, window=window,
        interpret=(backend == JoinBackend.PALLAS_INTERPRET))


# --------------------------------------------------------------------- #
# Mask -> (a_idx, b_idx) pair extraction and free-slot allocation.
# --------------------------------------------------------------------- #
def extract_pairs(mask: jnp.ndarray, max_new: int):
    """Top-``max_new`` (a, b) index pairs of a boolean join mask.

    Returns ``(a_idx, b_idx, pair_valid, n_dropped)`` with static length
    ``max_new``.  Uses a flattened ``nonzero`` with a static size; pairs
    beyond ``max_new`` are counted as dropped (overflow) — the production
    backpressure path.
    """
    flat = mask.reshape(-1)
    n_true = jnp.sum(flat, dtype=jnp.int32)
    (idx,) = jnp.nonzero(flat, size=max_new, fill_value=-1)
    pair_valid = idx >= 0
    cb = mask.shape[1]
    safe = jnp.maximum(idx, 0)
    a_idx = safe // cb
    b_idx = safe % cb
    n_dropped = jnp.maximum(n_true - max_new, 0)
    return a_idx, b_idx, pair_valid, n_dropped


def alloc_slots(valid: jnp.ndarray, need_valid: jnp.ndarray, max_new: int):
    """Allocate up to ``max_new`` free slots (``valid == False``).

    ``need_valid`` is the bool mask of requested appends (length max_new).
    Returns ``(slot_idx, ok, n_dropped)``: ``slot_idx`` is int32 of shape
    [max_new] (slot for each request, -1 when not granted), ``ok`` marks
    granted requests.  Requests beyond the number of free slots drop.
    """
    (free,) = jnp.nonzero(~valid, size=max_new, fill_value=-1)
    # compact requests: the i-th requested append takes the i-th free slot
    req_rank = jnp.cumsum(need_valid.astype(jnp.int32)) - 1
    slot_for_req = jnp.where(
        need_valid, jnp.take(free, jnp.clip(req_rank, 0, max_new - 1),
                             mode="clip"), -1)
    ok = need_valid & (slot_for_req >= 0)
    n_dropped = jnp.sum(need_valid & (slot_for_req < 0), dtype=jnp.int32)
    return slot_for_req, ok, n_dropped
