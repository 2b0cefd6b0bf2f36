"""Data generators, one module per configuration's ``generator`` key.

Each module defines ``generate(n, seed) -> np.ndarray`` of shape
(n, dim), float64. The harness loads the module by the name the
configuration file gives, so a new deployment adds a module here and
edits none.
"""
