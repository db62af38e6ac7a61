"""Device ms per iteration of the step's ops that run a collective
(all-to-all, all-reduce, all-gather, collective-permute, reduce-scatter),
sync or async, fused or not, averaged over the chips.  A fused collective is
found by the opcodes its fusion runs (hlo.op_kinds); an async ``-start`` /
``-done`` pair by its instruction name, since op_kinds follows ``calls=``
only for fusions.  A sync collective's time holds its wait for the slowest
chip (layer: exchange, core/placement.py and exchange/)."""

COLLECTIVES = ["all-to-all", "all-reduce", "all-gather", "collective-permute",
               "reduce-scatter"]
OPCODES = [op + suffix for op in COLLECTIVES for suffix in ("", "-start", "-done")]
# instruction names spell the opcode with '-' or '_' (``all_to_all.4``)
PATTERNS = ["^(" + "|".join(op.replace("-", "[-_]") for op in COLLECTIVES) + ")"]


def read(r):
    return r.ms_per_iter(PATTERNS, OPCODES)
