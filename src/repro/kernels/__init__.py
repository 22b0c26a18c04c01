"""Pallas kernels for the simulator's two hot paths.

- ``scout_step`` / ``ref`` / ``ops``: Algorithm-1 scout routing — the
  decision step and the whole DFS walk around it (a Pallas kernel per
  walk, the same walk as XLA ops), the gather-based jnp oracle of one
  step, and the jitted batch router.
- ``batched_step``: the lane-tiled wrapper that runs the batched static
  step from ``ssd.sim`` as a ``pl.pallas_call`` (lanes on the grid,
  each tick's gathered node tables in per-instance blocks).
- ``onehot``: gather-free one-hot compare-and-reduce lookups shared by
  the XLA and Pallas paths.
- ``backend``: interpret-mode selection (Pallas has no CPU compiler, so
  CPU runs interpret=True; accelerators compile).
"""
