"""Pallas TPU kernels with their pure-jnp references (``ref.py``).

No model calls them yet: the served path runs the jnp/XLA versions.  Each
kernel takes ``interpret=`` explicitly; tests run them in interpret mode on
the CPU, and ``tests/test_tpu_compile.py`` compiles them for a described
TPU topology.
"""
