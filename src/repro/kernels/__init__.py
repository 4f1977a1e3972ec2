"""Pallas TPU kernels for the framework's compute hot spots.

compat_join     The paper's inner loop: compatibility join between a
                partial-match table and a candidate table (edge batch or
                delta rows).  Fuses the per-slot-pair compare/reduce so
                the [CA, CB, NV] broadcast never exists in HBM.
segment_reduce  GNN message passing: gather(edge src) -> segment reduce
                (sum/max/mean) over destination nodes.
embedding_bag   RecSys: fused multi-hot gather + segment-sum over huge
                embedding tables.

Each kernel ships: ``kernel.py`` (pl.pallas_call + BlockSpec tiling),
``ops.py`` (jit'd public wrapper with padding + interpret switch) and
``ref.py`` (pure-jnp oracle).  On the CPU the compat_join kernels run
in interpret mode (interpret=True) against their oracle; the compiled
compat_join path runs on TPU v5e, where the platform selects it
(``repro.core.join.resolve_backend``) and ``chip_smoke.py`` checks it.
"""
