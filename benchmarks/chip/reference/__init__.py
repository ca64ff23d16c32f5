"""Plain references the benchmark holds the round loop to.

Straightforward jax.numpy / numpy re-statements of the paper's math, kept
with the benchmark so that no change to the program can move them. They
import nothing from `repro`. Every function takes a `dtype` (float32 by
default, run under highest matmul precision); the control passes the
nearest lower precision (bfloat16, or float32 for the float64 planner).
"""
