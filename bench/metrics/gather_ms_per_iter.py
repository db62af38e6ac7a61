"""Device ms per iteration of the ops that gather: XLA gathers, fused or
not, among them the source-value gather ahead of the ELL kernel (layer:
gather, kernels/ell_spmv/ops.py)."""

PATTERNS = [r"^gather"]
OPCODES = ["gather"]


def read(r):
    return r.ms_per_iter(PATTERNS, OPCODES)
