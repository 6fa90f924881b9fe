"""Pallas TPU kernels for the SparseInfer hot path (predictor + sparse MLP).

Each kernel: <name>.py (pl.pallas_call + BlockSpec).  ops.py holds the
jitted, backend-dispatching wrappers used by the rest of the framework: on
the CPU they run the kernels in the Pallas interpreter, which the tests
check against the pure-jnp oracles in ref.py; on a TPU they compile for the
chip and never substitute an oracle.  tests/test_chip_compile.py compiles
the main-path kernels at ProSparse-Llama2-7B widths for a described v5e.
"""
