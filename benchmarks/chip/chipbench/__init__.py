"""The chip benchmark of the GenFV round loop: cell loading, inputs and
weights from the seed, warm-up, the measured window, the trace reduction
and the comparison with the plain references in `reference/`."""
