"""Device ms per iteration of the ops that scatter: XLA scatters, fused or
not, and the scatter-combine kernels (layer: scatter and assign,
kernels/scatter_combine and placement.py)."""

PATTERNS = [r"scatter"]
OPCODES = ["scatter"]


def read(r):
    return r.ms_per_iter(PATTERNS, OPCODES)
