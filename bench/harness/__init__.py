"""The benchmark's general machinery: device check, weights, traffic,
the cell runners, the plain reference and the trace reduction. What
depends on a model's architecture is in ``blocks/``, one module a block."""
