"""Pallas TPU kernels for the compute hot spots of the serving/training
substrate. The PAPER's contribution is the I/O architecture (core/), not a
kernel — these exist because the framework's models need fast attention,
SSD scans and paged-KV decode on the TPU target. Each kernel ships with
``ops.py`` (jit wrapper; compiled by default, ``interpret=True`` runs the
Pallas interpreter) and ``ref.py`` (pure-jnp oracle) and a shape/dtype
sweep test asserting allclose.
"""
