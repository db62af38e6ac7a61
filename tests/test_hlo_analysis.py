"""Trip-count-aware collective accounting (dry-run roofline input)."""
import os
import subprocess
import sys

import pytest

from repro.launch.hlo_analysis import collective_totals, parse_computations

FAKE_HLO = """
HloModule jit_step, entry_computation_layout={()->f32[8]}

%cond.1 (arg.0: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(28)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body.2 (arg.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p2 = (s32[], f32[8]) parameter(0)
  %x = f32[8] get-tuple-element(%p2), index=1
  %ag = f32[8]{0} all-gather(%x), replica_groups={}, dimensions={0}
  %i2 = s32[] get-tuple-element(%p2), index=0
  ROOT %t = (s32[], f32[8]) tuple(%i2, %ag)
}

ENTRY %main.3 () -> f32[8] {
  %init = (s32[], f32[8]) tuple()
  %w = (s32[], f32[8]) while(%init), condition=%cond.1, body=%body.2
  %y = f32[8] get-tuple-element(%w), index=1
  %ar = f32[8]{0} all-reduce(%y), to_apply=%add.9
  ROOT %r = f32[8] copy(%ar)
}
"""


def test_parse_computations_splits_blocks():
    comps = parse_computations(FAKE_HLO)
    assert {"cond.1", "body.2", "main.3"} <= set(comps)
    assert comps["main.3"]["entry"]


def test_while_trip_count_multiplies_body_collectives():
    out = collective_totals(FAKE_HLO)
    # body all-gather: 32B x 28 trips; entry all-reduce: 32B x 1
    assert out["bytes"]["all-gather"] == 32 * 28
    assert out["bytes"]["all-reduce"] == 32
    assert out["raw_bytes"]["all-gather"] == 32


def test_real_scan_collectives_counted():
    """End-to-end on a real compiled program: an FSDP-style all-gather inside
    a 6-step scan must be counted ~6x (subprocess: forces 4 host devices)."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.hlo_analysis import collective_totals
from repro.core.mesh import worker_mesh
mesh = worker_mesh(4, "model")
sh = NamedSharding(mesh, P(None, "model"))
rep = NamedSharding(mesh, P())
def f(x, ws):
    def body(c, w):
        return jnp.tanh(c @ w), ()
    out, _ = jax.lax.scan(body, x, ws)
    return out
x = jax.ShapeDtypeStruct((64, 64), jnp.float32, sharding=rep)
ws = jax.ShapeDtypeStruct((6, 64, 64), jnp.float32, sharding=NamedSharding(mesh, P(None, None, "model")))
c = jax.jit(f, in_shardings=(rep, NamedSharding(mesh, P(None, None, "model"))), out_shardings=rep).lower(x, ws).compile()
out = collective_totals(c.as_text())
total = out["bytes"]["total"]
raw = out["raw_bytes"]["total"]
assert raw > 0, "collectives inside the scan body must be found"
# body collective x6 trips (+ entry-level ops once): adjusted >> raw
assert total >= 3 * raw, (total, raw)
print("TRIPS-OK", total, raw)
"""
    out = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                         timeout=300,
                         env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
                         cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert "TRIPS-OK" in out.stdout, out.stderr[-1500:]
