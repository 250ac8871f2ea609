"""The benchmark's general machinery: device check, weights, traffic,
the cell runners, the plain reference and the trace reduction."""
