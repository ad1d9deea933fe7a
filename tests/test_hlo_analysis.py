"""HLO parser: loop trip counts, dot FLOPs, collective bytes (subprocess
tests with a multi-device mesh; known-answer validation)."""
import pytest

from repro.parallel.hlo_analysis import _shape_bytes, _shape_dims, analyze_hlo
from tests._subproc import check


def test_shape_bytes_parsing():
    assert _shape_bytes("f32[32,128]{1,0}") == 32 * 128 * 4
    assert _shape_bytes("bf16[8]") == 16
    assert _shape_bytes("(f32[4,4], s32[2])") == 64 + 8
    assert _shape_bytes("pred[]") == 1      # scalar = one element
    assert _shape_dims("bf16[2,3,4]{2,1,0}") == [2, 3, 4]


SCAN_PROG = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.parallel.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(2, 4)
D, L, B = 128, 6, 64
def f(x, ws):
    def body(c, w):
        y = c @ w
        return jax.lax.with_sharding_constraint(
            y, NamedSharding(mesh, P("data", "model"))), None
    y, _ = jax.lax.scan(body, x, ws)
    return y.sum()
xs = jax.ShapeDtypeStruct((B, D), jnp.float32)
ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
with mesh:
    c = jax.jit(f, in_shardings=(
        NamedSharding(mesh, P("data", None)),
        NamedSharding(mesh, P(None, None, "model")))).lower(xs, ws).compile()
st = analyze_hlo(c.as_text())
print("TRIPS", st.trip_counts)
print("FLOPS", st.dot_flops)
print("EXPECTED", 2 * B * D * D * L / 8)
print("COLL", sorted(st.bytes_by_kind))
"""


@pytest.mark.slow
def test_scan_flops_and_trips_exact():
    out = check(SCAN_PROG, n_devices=8)
    lines = dict(l.split(" ", 1) for l in out.strip().splitlines())
    assert lines["TRIPS"] == "[6]"
    assert float(lines["FLOPS"]) == float(lines["EXPECTED"])
    assert "all-gather" in lines["COLL"] or "all-reduce" in lines["COLL"]


def test_analyze_empty():
    st = analyze_hlo("")
    assert st.dot_flops == 0 and st.total_collective_bytes == 0
