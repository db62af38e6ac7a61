"""Bytes of the prepared matrix pytree (every device leaf's nbytes, global
over a mesh) per directed edge slot (layer: prepare / packing)."""


def read(r):
    return r.matrix_bytes / r.edge_slots if r.matrix_bytes else None
