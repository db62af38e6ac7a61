"""Pure-jnp oracle for the ELL sparse-region GIM-V kernel."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def ell_gimv_ref(cols, w, v, *, semiring: str, out_dtype=None):
    """Slot-major tables cols/w [*L, D, R], v [N] -> r [*L, R]."""
    out_dtype = out_dtype or v.dtype
    valid = cols >= 0
    safe = jnp.where(valid, cols, 0)
    vals = v[safe]
    if semiring == "plus_times":
        x = (w * vals) if w is not None else vals
        x = jnp.where(valid, x, 0).astype(out_dtype)
        return jnp.sum(x, axis=-2)
    if semiring in ("min_plus", "max_plus"):
        x = (w + vals) if w is not None else vals
        ident = np.inf if semiring == "min_plus" else -np.inf
        x = jnp.where(valid, x, ident).astype(out_dtype)
        return jnp.min(x, axis=-2) if semiring == "min_plus" else jnp.max(x, axis=-2)
    if semiring == "min_src":
        ident = (np.inf if jnp.issubdtype(jnp.dtype(out_dtype), jnp.floating)
                 else np.iinfo(out_dtype).max)
        x = jnp.where(valid, vals.astype(out_dtype), jnp.array(ident, out_dtype))
        return jnp.min(x, axis=-2)
    raise ValueError(semiring)


def ell_gimv_multi_ref(cols, w, v, *, semiring: str, out_dtype=None):
    """Vmapped oracle for the multi-query kernel: v [N, Q] -> r [*L, R, Q]."""
    import jax

    return jax.vmap(
        lambda col: ell_gimv_ref(cols, w, col, semiring=semiring, out_dtype=out_dtype),
        in_axes=1, out_axes=-1,
    )(v)
