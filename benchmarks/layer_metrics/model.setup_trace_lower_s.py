"""Seconds before the window opened in which the process traced a function to a jaxpr or lowered one to MLIR: the union of the program spans jax.trace and jax.lower (inner jits nest inside outer ones, so a union and not a sum). Python work of setup_s that a warm compile cache does not save."""


def read(c):
    from benchmarks import runtime_spans as rs

    return rs.setup_union_s(c, ("jax.trace", "jax.lower"))
