"""Operations and bytes per kernel call, from shapes: one module per
kernel, each with ``cost(...) -> (flop, bytes)``. Bytes are what the
algorithm has to move between HBM and the core; operations are the
multiply-adds the algorithm needs, counted as two."""
