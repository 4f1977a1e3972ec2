"""Kernel contract checker: static proofs over every ``pallas_call``.

Machine-checked kernel contracts, so a block shape the TPU compiler
would refuse is caught on the CPU, before any chip run.  This pass
proves, for every kernel entry point in ``kernels/*/kernel.py`` and
over the *reachable shape lattice* — pow-2 capacities (the serving stack
quantizes every table axis with ``runtime.straggler.quantize_pow2``,
floor 8) × all ``choose_tiles`` outputs × slot-stack depths:

KC101  tile divisibility: the padded capacity each op wrapper feeds the
       kernel is an exact tile multiple and every grid extent is ≥ 1;
KC102  block shapes the TPU compiler accepts: every VMEM block's last
       two (non-squeezed) dims are (sublane, 128)-divisible or equal to
       the array's — sublane 8 for int32, 32 for the int8 mask — and a
       1-D block is the whole array (``block_shape_finding``; the rule
       Mosaic enforces, which a ``(tile,)`` int32 block or a ``(1, tile)``
       per-slot block violates);
KC103  index-map bounds: each BlockSpec's ``index_map`` (mirrored here,
       declaratively, from the kernel source) stays in bounds for every
       grid point — ``index*block + block <= padded array dim`` on every
       axis, including the data-dependent embedding-bag maps, which are
       proven by interval argument from their documented preconditions;
KC104  cursor safety for ``compat_join_pairs``: the emit clamp
       ``n_emit = min(n_tile, max(max_new - base, 0))`` implies every
       write lands strictly below ``max_new`` — and inside the
       ``[out_rows(max_new), 128]`` output block — for any base in
       [0, CA·CB] and any per-tile count in [0, TA·TB]; checked
       algebraically at the interval extremes, after asserting the
       clamp expression is actually present in the kernel source;
KC105  kernel-vs-ref agreement: ``jax.eval_shape`` abstract evaluation
       of the public ops against the pure-jnp ``ref.py`` oracles (and
       their vmapped forms against the stacked 3-D-grid kernels) —
       identical output trees, shapes and dtypes, with zero FLOPs run.

KC100 (warning) flags any ``pallas_call`` site in a kernels package that
has no declarative contract here — new kernels must register one.

``jax.eval_shape`` does trace the kernel bodies (on CPU, no lowering,
no execution), so KC105 also catches rank/dtype bugs *inside* kernel
bodies, not just in the wrappers.
"""

from __future__ import annotations

import ast
import itertools
import os

import numpy as np

from repro.analysis.findings import ERROR, WARNING, Finding

# Entry points with a declarative contract below.  KC100 fires for any
# pallas_call in kernels/*/kernel.py outside these functions.
MODELED_ENTRY_POINTS = frozenset({
    "compat_mask_kernel", "compat_join_pairs_kernel",
    "segment_sum_kernel", "embedding_bag_kernel",
})

# Reachable shape lattice.  Capacities are pow-2 (quantize_pow2, lo=8);
# slot-stack depths come from plan_signature grouping in core.multi.
CAPS_FULL = tuple(2 ** k for k in range(3, 13))          # 8 .. 4096
CAPS_FAST = (8, 64, 256, 4096)
SLOTS = (1, 2, 4, 8)
MAX_NEW = (64, 256, 1024, 4096)
WIDTHS = (1, 2, 3, 4)                                    # nv / ne columns
_LANE = 128                                              # TPU lane width

# Representative (a_batched, b_batched) sets for the packed operands:
# both shared (an unbatched call), both per-slot, and each one-sided mix.
FLAG_SETS = ((False, False), (True, True), (True, False), (False, True))


def _finding(rule, severity, symbol, message, path="", line=0):
    return Finding(pass_name="kernel", rule=rule, severity=severity,
                   path=path, line=line, symbol=symbol, message=message)


# --------------------------------------------------------------------- #
# pallas_call site discovery (KC100 + n_pallas_sites)
# --------------------------------------------------------------------- #
def discover_pallas_sites(kernels_root: str) -> list[tuple[str, str, int]]:
    """All ``pallas_call`` sites in kernels/*/kernel.py as
    (repo-relative path, enclosing function name, line)."""
    sites = []
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(
        os.path.join(kernels_root, os.pardir))))
    for dirpath, _d, files in sorted(os.walk(kernels_root)):
        for fn in sorted(files):
            if fn != "kernel.py":
                continue
            path = os.path.join(dirpath, fn)
            with open(path) as fh:
                tree = ast.parse(fh.read(), filename=path)
            rel = os.path.relpath(path, repo_root)
            func_stack: list[str] = []

            def visit(node):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    func_stack.append(node.name)
                    for c in ast.iter_child_nodes(node):
                        visit(c)
                    func_stack.pop()
                    return
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "pallas_call"):
                    sites.append((rel, func_stack[-1] if func_stack
                                  else "<module>", node.lineno))
                for c in ast.iter_child_nodes(node):
                    visit(c)

            visit(tree)
    return sites


# --------------------------------------------------------------------- #
# Declarative BlockSpec contracts (mirrored from kernel.py)
# --------------------------------------------------------------------- #
def _bounds_ok(grid, specs):
    """Exhaustively check index*block + block <= dim for every grid
    point.  ``specs`` is [(name, array_shape, block_shape, index_map)]
    with index_map taking the grid tuple and returning block indices
    (a squeezed ``None`` block dim indexes single elements)."""
    bad = []
    for point in itertools.product(*(range(g) for g in grid)):
        for name, array_shape, block_shape, index_map in specs:
            idx = index_map(*point)
            for ax, (i, b, dim) in enumerate(
                    zip(idx, block_shape, array_shape)):
                b = 1 if b is None else b
                if i < 0 or i * b + b > dim:
                    bad.append((name, point, ax, i, b, dim))
    return bad


def block_shape_finding(name, array_shape, block_shape, sublane=8):
    """KC102 for one VMEM block: the rule Mosaic enforces when it lowers
    a ``pallas_call`` for the TPU (``None`` dims are squeezed away).

    * 2-D and up: the block's second-to-last dim is a multiple of the
      dtype's sublane tile (8 for int32, 32 for int8) or equals the
      array's dim, and its last dim is a multiple of 128 or equals the
      array's dim;
    * 1-D: the block is the whole array — a ``(tile,)`` block of a
      longer 1-D array gets a Mosaic tiling that does not match XLA's
      layout of the operand, and the compile is refused.

    Returns a ``Finding`` for a violating block, else None.
    """
    dims = [(a, b) for a, b in zip(array_shape, block_shape)
            if b is not None]
    if len(dims) == 1:
        (a1, b1), = dims
        ok = b1 == a1
    else:
        (a2, b2), (a1, b1) = dims[-2], dims[-1]
        ok = ((b2 % sublane == 0 or b2 == a2)
              and (b1 % _LANE == 0 or b1 == a1))
    if ok:
        return None
    return _finding(
        "KC102", ERROR, name,
        f"block {tuple(block_shape)} of array {tuple(array_shape)} breaks "
        f"the TPU block rule (last two dims divisible by ({sublane}, "
        f"{_LANE}) or equal to the array's; a 1-D block must be the "
        f"whole array)")


def _compat_specs(n_slots, cap, cbp, ta, tb, widths, a_batched, b_batched,
                  max_new=None, blocks=(1, 1), ext=None):
    """The ``(slot, A-block, B-block)`` grid and specs of both compat_join
    kernels, mirrored from ``kernel._side_spec`` and the out_specs:
    ``(grid, [(name, array_shape, block_shape, index_map, sublane)])``.
    Packed operands are ``[S?, n_tiles, K, tile]`` with ``blocks`` =
    (A, B) tiles per block (one for the mask kernel); a per-slot operand
    has a squeezed slot dim, a shared one ignores the slot coordinate.
    The pairs kernel clamps a block index to the last block below the
    extent, ``ext`` (A, B) live tiles (None: the whole side)."""
    from repro.kernels.compat_join.kernel import out_rows
    nva, nea, nvb, neb = widths
    ka, kb = nva + nea + 1, nvb + neb + 1
    specs, grid = [], [n_slots]
    for name, k, c, t, batched, blk, e, pick in (
            ("a", ka, cap, ta, a_batched, blocks[0], ext and ext[0],
             lambda i, j: i),
            ("b", kb, cbp, tb, b_batched, blocks[1], ext and ext[1],
             lambda i, j: j)):
        n = c // t
        grid.append(n // blk)
        last = (n if e is None else max(-(-e // blk) - 1, 0))

        def idx(i, j, pick=pick, last=last):
            return min(pick(i, j), last)
        if batched:
            specs.append((name, (n_slots, n, k, t), (None, blk, k, t),
                          lambda s, i, j, idx=idx: (s, idx(i, j), 0, 0), 8))
        else:
            specs.append((name, (n, k, t), (blk, k, t),
                          lambda s, i, j, idx=idx: (idx(i, j), 0, 0), 8))
    if max_new is None:
        specs.append(("mask_out", (n_slots, cap, cbp), (None, ta, tb),
                       lambda s, i, j: (s, i, j), 32))      # int8
    else:
        r = out_rows(max_new)
        for name in ("a_out", "b_out"):
            specs.append((name, (n_slots, r, _LANE), (None, r, _LANE),
                          lambda s, i, j: (s, 0, 0), 8))
        specs.append(("n_out", (n_slots, 1, _LANE), (None, 1, _LANE),
                      lambda s, i, j: (s, 0, 0), 8))
    return tuple(grid), specs


def check_tiles_and_bounds(fast: bool = False) -> list[Finding]:
    """KC101/KC102/KC103 over the reachable lattice for the compat
    kernels, plus the fixed-tile segment_reduce / embedding_bag grids."""
    from repro.kernels.compat_join.kernel import (
        _ceil_to, choose_tiles, mask_tile_a)

    findings: list[Finding] = []
    caps = CAPS_FAST if fast else CAPS_FULL

    # --- compat_join: full choose_tiles lattice, both kernels ---
    for ca, cb in itertools.product(caps, caps):
        ta, tb = choose_tiles(ca, cb)
        sym = f"choose_tiles({ca},{cb})"
        widths = (2, 1, 1, 1)
        bad, refused = [], []
        for max_new, ta_k in ((None, mask_tile_a(ta)), (MAX_NEW[0], ta)):
            cap, cbp = _ceil_to(ca, ta_k), _ceil_to(cb, tb)
            if cap % ta_k or cbp % tb or cap // ta_k < 1 or cbp // tb < 1:
                findings.append(_finding(
                    "KC101", ERROR, sym,
                    f"padded caps ({cap},{cbp}) not exact multiples of "
                    f"tiles ({ta_k},{tb}) or empty grid"))
                continue
            n_a, n_b = cap // ta_k, cbp // tb
            # the mask kernel's one tile per block; the pairs kernel's
            # whole sides, gridded A, gridded both, at extents 0, 1, all
            layouts = [((1, 1), None)]
            if max_new is not None:
                layouts = [(blk, ext)
                           for blk in ((n_a, n_b), (1, n_b), (1, 1))
                           for ext in (None, (0, 0), (1, 1))]
            for (blocks, ext), n_slots, flags in itertools.product(
                    layouts, SLOTS if not fast else SLOTS[:2],
                    FLAG_SETS if not fast else FLAG_SETS[:2]):
                grid, specs = _compat_specs(
                    n_slots, cap, cbp, ta_k, tb, widths, *flags,
                    max_new=max_new, blocks=blocks, ext=ext)
                bad += _bounds_ok(grid, [sp[:4] for sp in specs])
                refused += [f for sp in specs
                            if (f := block_shape_finding(
                                f"{sym}.{sp[0]}", sp[1], sp[2], sp[4]))]
        findings += refused[:3]
        for name, point, ax, i, b, dim in bad[:3]:
            findings.append(_finding(
                "KC103", ERROR, sym,
                f"index_map of {name} out of bounds at grid {point}: "
                f"axis {ax} block {i}*{b}+{b} > {dim}"))

    # --- segment_reduce: fixed 512/256 tiles, padded-multiple contract ---
    from repro.kernels.segment_reduce.kernel import TILE_E, TILE_N
    seg_lat = [(TILE_E * a, TILE_N * b, d)
               for a in (1, 4) for b in (1, 4) for d in (8, 128)]
    for e, n, d in seg_lat:
        grid = (n // TILE_N, e // TILE_E)
        sym = f"segment_sum_kernel(E={e},N={n},D={d})"
        if e % TILE_E or n % TILE_N or grid[0] < 1 or grid[1] < 1:
            findings.append(_finding(
                "KC101", ERROR, sym, "padded-multiple precondition "
                "violated inside the checker's own lattice"))
            continue
        specs = [
            ("dst", (e,), (TILE_E,), lambda i, j: (j,)),
            ("msg", (e, d), (TILE_E, d), lambda i, j: (j, 0)),
            ("out", (n, d), (TILE_N, d), lambda i, j: (i, 0)),
        ]
        for name, point, ax, i, b, dim in _bounds_ok(grid, specs)[:3]:
            findings.append(_finding(
                "KC103", ERROR, sym,
                f"index_map of {name} out of bounds at grid {point}"))

    # --- embedding_bag: data-dependent maps, interval proof ---
    # Preconditions (documented in kernel.py): ids in [-1, V-1] with the
    # map clamping to max(ids[i], 0); bags in [0, n_bags-1].
    for v, n_bags, d in ((16, 4, 8), (4096, 512, 64)):
        sym = f"embedding_bag_kernel(V={v},B={n_bags},D={d})"
        lo, hi = max(-1, 0), v - 1          # after clamp: [0, V-1]
        if not (0 <= lo and hi * 1 + 1 <= v):
            findings.append(_finding(
                "KC103", ERROR, sym,
                "clamped table index interval exceeds [0, V)"))
        if not (0 <= 0 and (n_bags - 1) * 1 + 1 <= n_bags):
            findings.append(_finding(
                "KC103", ERROR, sym,
                "bag output index interval exceeds [0, n_bags)"))
    return findings


# --------------------------------------------------------------------- #
# KC104: pair-output cursor interval proof
# --------------------------------------------------------------------- #
_CLAMP_EXPR = "jnp.minimum(n_tile, jnp.maximum(max_new - base, 0))"


def check_smem_cursor(fast: bool = False) -> list[Finding]:
    """Prove the pairs kernel's emit loop never writes at or beyond
    ``max_new``, for any cursor value the grid can produce, and that
    every write's ``(row, lane) = divmod(p, 128)`` lands inside the
    ``[out_rows(max_new), 128]`` output block."""
    import repro.kernels.compat_join.kernel as K
    findings: list[Finding] = []

    src = open(K.__file__).read()
    if _CLAMP_EXPR not in src:
        findings.append(_finding(
            "KC104", ERROR, "compat_join_pairs._pairs_body",
            f"emit clamp `{_CLAMP_EXPR}` not found in kernel source — "
            f"the SMEM cursor bound proof no longer applies"))
        return findings

    caps = CAPS_FAST if fast else CAPS_FULL
    for ca, cb in itertools.product(caps, caps):
        ta, tb = K.choose_tiles(ca, cb)
        cap, cbp = K._ceil_to(ca, ta), K._ceil_to(cb, tb)
        n_tile_max = ta * tb
        for max_new in MAX_NEW:
            rows = K.out_rows(max_new)
            # cursor extremes: 0, around the clamp knee, and the
            # absolute maximum (every pair of every tile matched)
            bases = {0, max(0, max_new - 1), max_new, max_new + 1,
                     cap * cbp}
            for base in bases:
                for n_tile in (0, 1, n_tile_max):
                    n_emit = min(n_tile, max(max_new - base, 0))
                    last = base + n_emit - 1
                    if n_emit > 0 and (last >= max_new
                                       or last // K._LANE >= rows):
                        findings.append(_finding(
                            "KC104", ERROR,
                            f"compat_join_pairs(ca={ca},cb={cb},"
                            f"max_new={max_new})",
                            f"cursor write base={base} k={n_emit - 1} "
                            f"reaches index {last} (max_new={max_new}, "
                            f"{rows} output rows)"))
    return findings


# --------------------------------------------------------------------- #
# KC105: kernel-vs-ref abstract evaluation agreement
# --------------------------------------------------------------------- #
def _tree_sig(tree):
    import jax
    return jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), tree)


def check_kernel_ref_agreement(fast: bool = False) -> list[Finding]:
    """``jax.eval_shape`` the public ops against their ref oracles."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.compat_join import ops as cj_ops
    from repro.kernels.compat_join import ref as cj_ref
    from repro.kernels.embedding_bag import kernel as eb_k
    from repro.kernels.embedding_bag import ref as eb_ref
    from repro.kernels.segment_reduce import kernel as sr_k
    from repro.kernels.segment_reduce import ref as sr_ref

    findings: list[Finding] = []
    S = jax.ShapeDtypeStruct
    i32 = jnp.int32

    def compare(sym, fk, fr, *args):
        try:
            got = _tree_sig(jax.eval_shape(fk, *args))
        except Exception as exc:                       # trace failure
            findings.append(_finding(
                "KC105", ERROR, sym,
                f"kernel path failed abstract evaluation: {exc!r}"))
            return
        want = _tree_sig(jax.eval_shape(fr, *args))
        if got != want:
            findings.append(_finding(
                "KC105", ERROR, sym,
                f"kernel/ref signature mismatch: {got} != {want}"))

    # compat_join: include a non-pow-2 point to exercise the padding path
    points = [(8, 8), (64, 128), (100, 37)]
    if not fast:
        points += [(256, 256), (1024, 512)]
    nva, nea, nvb, neb = 2, 2, 1, 1
    rel = np.zeros((nva, nvb), bool)
    rel[0, 0] = True
    trel = np.zeros((nea, neb), np.int8)
    trel[-1, 0] = -1
    for ca, cb in points:
        # valid is bool by contract (core.join.compat_mask_ref signature)
        a = (S((ca, nva), i32), S((ca, nea), i32), S((ca,), jnp.bool_))
        b = (S((cb, nvb), i32), S((cb, neb), i32), S((cb,), jnp.bool_))
        sym = f"compat_mask(ca={ca},cb={cb})"
        compare(sym,
                lambda *t: cj_ops.compat_mask(*t, rel, trel, window=30),
                lambda *t: cj_ref.compat_mask(*t, rel, trel, window=30),
                *a, *b)
        sym = f"compat_join_pairs(ca={ca},cb={cb})"
        compare(sym,
                lambda *t: cj_ops.compat_join_pairs(
                    *t, rel, trel, 256, window=30),
                lambda *t: cj_ref.compat_join_pairs(
                    *t, rel, trel, 256, window=30),
                *a, *b)

    # vmapped -> stacked 3-D-grid kernel (per-slot windows)
    for n_slots in (SLOTS[:2] if fast else SLOTS):
        ca, cb = 64, 128
        a = (S((n_slots, ca, nva), i32), S((n_slots, ca, nea), i32),
             S((n_slots, ca), jnp.bool_))
        b = (S((n_slots, cb, nvb), i32), S((n_slots, cb, neb), i32),
             S((n_slots, cb), jnp.bool_))
        w = S((n_slots,), i32)

        def k_mask(ba, ea, va, bb, eb, vb, win):
            return cj_ops.compat_mask(ba, ea, va, bb, eb, vb, rel, trel,
                                      window=win)

        def r_mask(ba, ea, va, bb, eb, vb, win):
            return cj_ref.compat_mask(ba, ea, va, bb, eb, vb, rel, trel,
                                      window=win)

        def k_pairs(ba, ea, va, bb, eb, vb, win):
            return cj_ops.compat_join_pairs(
                ba, ea, va, bb, eb, vb, rel, trel, 256, window=win)

        def r_pairs(ba, ea, va, bb, eb, vb, win):
            return cj_ref.compat_join_pairs(
                ba, ea, va, bb, eb, vb, rel, trel, 256, window=win)

        compare(f"vmap(compat_mask)(S={n_slots})",
                jax.vmap(k_mask), jax.vmap(r_mask), *a, *b, w)
        compare(f"vmap(compat_join_pairs)(S={n_slots})",
                jax.vmap(k_pairs), jax.vmap(r_pairs), *a, *b, w)

    # segment_reduce
    e, n, d = (512, 256, 8) if fast else (2048, 1024, 64)
    compare(f"segment_sum(E={e},N={n},D={d})",
            lambda dst, msg: sr_k.segment_sum_kernel(dst, msg, n),
            lambda dst, msg: sr_ref.segment_sum(dst, msg, n),
            S((e,), i32), S((e, d), jnp.float32))

    # embedding_bag (kernel takes the extra `first` marker input)
    t, v, nb, d = (16, 32, 4, 8) if fast else (128, 1024, 32, 64)
    compare(f"embedding_bag(T={t},V={v},B={nb},D={d})",
            lambda ids, bags, first, table: eb_k.embedding_bag_kernel(
                ids, bags, first, table, nb),
            lambda ids, bags, first, table: eb_ref.embedding_bag(
                ids, bags, table, nb),
            S((t,), i32), S((t,), i32), S((t,), i32),
            S((v, d), jnp.float32))
    return findings


# --------------------------------------------------------------------- #
# Entry point
# --------------------------------------------------------------------- #
def check_kernels(kernels_root: str | None = None, fast: bool = False
                  ) -> tuple[list[Finding], dict]:
    if kernels_root is None:
        kernels_root = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "kernels")
    findings: list[Finding] = []
    sites = discover_pallas_sites(kernels_root)
    for path, func, line in sites:
        if func not in MODELED_ENTRY_POINTS:
            findings.append(Finding(
                pass_name="kernel", rule="KC100", severity=WARNING,
                path=path, line=line, symbol=func,
                message="pallas_call without a declarative contract in "
                        "repro.analysis.kernel_check — register its "
                        "BlockSpecs in MODELED_ENTRY_POINTS"))
    findings += check_tiles_and_bounds(fast=fast)
    findings += check_smem_cursor(fast=fast)
    findings += check_kernel_ref_agreement(fast=fast)
    stats = {"n_pallas_sites": len(sites)}
    return findings, stats
