"""Device ms per iteration of the Pallas ELL kernels, whose HLO custom calls
take the kernel's name (layer: block kernels, kernels/ell_spmv)."""

PATTERNS = [r"^ell_gimv"]


def read(r):
    return r.ms_per_iter(PATTERNS)
