"""Loop-aware HLO cost analyzer: validated against XLA's own
cost_analysis on loop-free programs and against analytic counts on
scanned programs (where XLA's visitor counts bodies once)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.hlo_cost import analyze_hlo_text, parse_hlo
from repro.launch.mesh import make_mesh


def _compile(f, *sds):
    return jax.jit(f).lower(*sds).compile()


def test_matches_xla_on_loop_free():
    def f(a, b):
        return jnp.tanh(a @ b)
    c = _compile(f, jax.ShapeDtypeStruct((128, 256), jnp.float32),
                 jax.ShapeDtypeStruct((256, 64), jnp.float32))
    mine = analyze_hlo_text(c.as_text())
    xla = c.cost_analysis()
    np.testing.assert_allclose(mine["flops"], xla["flops"], rtol=0.05)


def test_scan_trip_count_multiplied():
    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        return jax.lax.scan(body, x, ws)[0]
    c = _compile(f, jax.ShapeDtypeStruct((64, 64), jnp.float32),
                 jax.ShapeDtypeStruct((12, 64, 64), jnp.float32))
    mine = analyze_hlo_text(c.as_text())
    expected = 12 * 2 * 64 ** 3
    assert abs(mine["flops"] - expected) / expected < 0.05
    assert not mine["warnings"]
    # XLA's own visitor counts the body once -- the reason this module
    # exists; if XLA ever fixes it, this assert flags the redundancy.
    assert c.cost_analysis()["flops"] < expected / 2


def test_nested_scan():
    def f(x):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ c2, None
            return jax.lax.scan(inner, c, None, length=3)[0], None
        return jax.lax.scan(outer, x, None, length=5)[0]
    c = _compile(f, jax.ShapeDtypeStruct((32, 32), jnp.float32))
    mine = analyze_hlo_text(c.as_text())
    expected = 15 * 2 * 32 ** 3
    assert abs(mine["flops"] - expected) / expected < 0.1


def test_dot_general_contracting_dims():
    def f(a, b):
        return jnp.einsum("bik,bkj->bij", a, b)
    c = _compile(f, jax.ShapeDtypeStruct((4, 32, 48), jnp.float32),
                 jax.ShapeDtypeStruct((4, 48, 16), jnp.float32))
    mine = analyze_hlo_text(c.as_text())
    expected = 2 * 4 * 32 * 48 * 16
    assert abs(mine["flops"] - expected) / expected < 0.05


def test_parse_hlo_computations():
    def f(x):
        return jax.lax.scan(lambda c, _: (c * 2, None), x, None,
                            length=4)[0]
    c = _compile(f, jax.ShapeDtypeStruct((8,), jnp.float32))
    comps = parse_hlo(c.as_text())
    assert len(comps) >= 2       # entry + loop body/cond at least
    entry = [k for k in comps if "main" in k]
    assert entry


def test_bytes_reasonable_for_elementwise():
    def f(a):
        return a * 2.0 + 1.0
    c = _compile(f, jax.ShapeDtypeStruct((1024, 1024), jnp.float32))
    mine = analyze_hlo_text(c.as_text())
    # one read + one write of 4MB, allow fusion-accounting slack
    assert 6e6 < mine["bytes"] < 2e7


# ---------------------------------------------------------------------------
# Edge cases on synthetic HLO text -- these feed the roofline numbers,
# so each accounting rule gets a direct, exactly-assertable fixture
# (compiled programs exercise them only incidentally)
# ---------------------------------------------------------------------------

_WHILE_KNOWN_TRIP = """
ENTRY %main (p0: f32[64]) -> f32[64] {
  %p0 = f32[64] parameter(0)
  %w = f32[64] while(%p0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
  ROOT %r = f32[64] add(%w, %w)
}

%body (bp: f32[64]) -> f32[64] {
  %bp = f32[64] parameter(0)
  ROOT %ba = f32[64] add(%bp, %bp)
}

%cond (cp: f32[64]) -> pred[] {
  %cp = f32[64] parameter(0)
  ROOT %cc = pred[] constant(false)
}
"""


def test_while_known_trip_count_from_backend_config():
    # XLA's own analysis (backend_config known_trip_count) outranks the
    # condition-computation heuristic: 7 body trips x 64 adds + the
    # root add, exactly
    mine = analyze_hlo_text(_WHILE_KNOWN_TRIP)
    assert mine["flops"] == 7 * 64 + 64
    assert not mine["warnings"]


_WHILE_COND_TRIP = """
ENTRY %main (p: (s32[], f32[32,32])) -> f32[32,32] {
  %p = (s32[], f32[32,32]) parameter(0)
  %w = (s32[], f32[32,32]) while(%p), condition=%cond2, body=%body2
  ROOT %out = f32[32,32] get-tuple-element(%w), index=1
}

%body2 (bp: (s32[], f32[32,32])) -> (s32[], f32[32,32]) {
  %bp = (s32[], f32[32,32]) parameter(0)
  %i = s32[] get-tuple-element(%bp), index=0
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  %x = f32[32,32] get-tuple-element(%bp), index=1
  %y = f32[32,32] dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[32,32]) tuple(%ip, %y)
}

%cond2 (cp: (s32[], f32[32,32])) -> pred[] {
  %cp = (s32[], f32[32,32]) parameter(0)
  %i2 = s32[] get-tuple-element(%cp), index=0
  %k = s32[] constant(9)
  ROOT %lt = pred[] compare(%i2, %k), direction=LT
}
"""


def test_while_trip_count_from_condition_constant():
    # no backend_config: the i < 9 condition (constant compared with
    # direction=LT) recovers trip 9.  Per trip: one 32x32x32 dot, the
    # counter add, the condition compare.
    mine = analyze_hlo_text(_WHILE_COND_TRIP)
    assert mine["flops"] == 9 * (2 * 32 ** 3 + 1 + 1)
    assert not mine["warnings"]


_WHILE_UNKNOWN_TRIP = """
ENTRY %main (p: f32[64]) -> f32[64] {
  %p = f32[64] parameter(0)
  ROOT %w = f32[64] while(%p), condition=%cond3, body=%body3
}

%body3 (bp: f32[64]) -> f32[64] {
  %bp = f32[64] parameter(0)
  ROOT %ba = f32[64] add(%bp, %bp)
}

%cond3 (cp: f32[64]) -> pred[] {
  %cp = f32[64] parameter(0)
  %s = f32[] constant(0)
  ROOT %gt = pred[] compare(%s, %s), direction=GT
}
"""


def test_while_unknown_trip_warns_and_counts_once():
    # data-dependent bound (no LT-vs-constant shape): counted exactly
    # once, and the under-count is surfaced in warnings -- never silent
    mine = analyze_hlo_text(_WHILE_UNKNOWN_TRIP)
    assert mine["flops"] == 64 + 1       # one body trip + one compare
    assert any("trip count unknown" in w for w in mine["warnings"])


_FUSION_SLICED_OPERAND = """
ENTRY %main (big: f32[1024,64], idx: s32[]) -> f32[16] {
  %big = f32[1024,64] parameter(0)
  %idx = s32[] parameter(1)
  %f = f32[16] fusion(%big, %idx), kind=kLoop, calls=%fused
  ROOT %r = f32[16] add(%f, %f)
}

%fused (fp0: f32[1024,64], fp1: s32[]) -> f32[16] {
  %fp0 = f32[1024,64] parameter(0)
  %fp1 = s32[] parameter(1)
  %ds = f32[1,16] dynamic-slice(%fp0, %fp1, %fp1), dynamic_slice_sizes={1,16}
  ROOT %rs = f32[16] reshape(%ds)
}
"""


def test_fusion_prices_sliced_operand_at_slice_size():
    # the 256KB table is consumed only by a dynamic-slice inside the
    # fusion: XLA reads 64 bytes, and so must the model -- pricing the
    # full buffer would claim a 3-orders-of-magnitude memory bound
    mine = analyze_hlo_text(_FUSION_SLICED_OPERAND)
    assert mine["bytes"] < 1e3
    full_table = 1024 * 64 * 4
    assert mine["bytes"] < full_table / 100


_FUSION_INTERNALS = """
ENTRY %main (a: f32[256,256]) -> f32[256,256] {
  %a = f32[256,256] parameter(0)
  ROOT %f = f32[256,256] fusion(%a), kind=kLoop, calls=%chain
}

%chain (cp: f32[256,256]) -> f32[256,256] {
  %cp = f32[256,256] parameter(0)
  %m = f32[256,256] multiply(%cp, %cp)
  %s = f32[256,256] add(%m, %cp)
  ROOT %t = f32[256,256] tanh(%s)
}
"""


def test_fusion_internal_operands_not_double_counted():
    # bytes touch HBM only at the fusion boundary (operand + result);
    # the three internal elementwise stages live in VMEM.  FLOPs still
    # count every internal op.
    n = 256 * 256
    mine = analyze_hlo_text(_FUSION_INTERNALS)
    assert mine["bytes"] == 2 * n * 4          # one read + one write
    assert mine["flops"] == 3 * n


def test_half_precision_byte_accounting():
    def hlo(dt):
        return (f"ENTRY %main (p: {dt}[1024]) -> {dt}[1024] {{\n"
                f"  %p = {dt}[1024] parameter(0)\n"
                f"  ROOT %a = {dt}[1024] add(%p, %p)\n"
                f"}}\n")
    by = {dt: analyze_hlo_text(hlo(dt))["bytes"]
          for dt in ("f32", "bf16", "f16")}
    assert by["f32"] == 3 * 1024 * 4           # two reads + one write
    assert by["bf16"] == by["f16"] == 3 * 1024 * 2


def test_collectives_counted_under_spmd():
    mesh = make_mesh((1,), ("x",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    def f(a):
        return a.sum()
    sh = NamedSharding(mesh, P("x"))
    c = jax.jit(f, in_shardings=sh).lower(
        jax.ShapeDtypeStruct((16, 16), jnp.float32)).compile()
    mine = analyze_hlo_text(c.as_text())
    assert "collective_bytes" in mine   # presence; 1-device may elide
