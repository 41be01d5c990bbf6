"""HLO-text analysis unit tests (synthetic snippets + a real compile)."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo import (collective_bytes, collective_group_sizes,
                              hbm_bytes, quadratic_traffic, shape_bytes,
                              split_computations)

SYNTH = """\
HloModule test

%add.clone (x.1: f32[], y.1: f32[]) -> f32[] {
  %x.1 = f32[] parameter(0)
  %y.1 = f32[] parameter(1)
  ROOT %add.2 = f32[] add(%x.1, %y.1)
}

%body (p.0: (s32[], f32[16,128])) -> (s32[], f32[16,128]) {
  %p.0 = (s32[], f32[16,128]) parameter(0)
  %iter = s32[] get-tuple-element(%p.0), index=0
  %buf = f32[16,128]{1,0} get-tuple-element(%p.0), index=1
  %ar = f32[16,128]{1,0} all-reduce(%buf), replica_groups=[4,4]<=[16], to_apply=%add.clone
  ROOT %t = (s32[], f32[16,128]) tuple(%iter, %ar)
}

%cond (p.1: (s32[], f32[16,128])) -> pred[] {
  %p.1 = (s32[], f32[16,128]) parameter(0)
  %i = s32[] get-tuple-element(%p.1), index=0
  %c = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

ENTRY %main (arg: f32[16,128]) -> f32[16,128] {
  %arg = f32[16,128]{1,0} parameter(0)
  %ag = f32[64,128]{1,0} all-gather(%arg), replica_groups=[4,4]<=[16], dimensions={0}
  %sl = f32[16,128]{1,0} slice(%ag), slice={[0:16], [0:128]}
  %tup = (s32[], f32[16,128]) tuple(%sl, %sl)
  %w = (s32[], f32[16,128]) while(%tup), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
  ROOT %out = f32[16,128]{1,0} get-tuple-element(%w), index=1
}
"""


class TestShapeBytes:
    def test_simple(self):
        assert shape_bytes("f32[16,128]{1,0}") == 16 * 128 * 4
        assert shape_bytes("bf16[4]") == 8
        assert shape_bytes("(f32[2], s32[3])") == 8 + 12
        assert shape_bytes("f32[]") == 4
        assert shape_bytes("pred[8]") == 8


class TestCollectives:
    def test_trip_count_weighting(self):
        d = collective_bytes(SYNTH)
        # all-gather once: 64*128*4 = 32768; all-reduce in 7-trip while body:
        # 7 * 16*128*4 = 57344
        assert d["all-gather"] == 64 * 128 * 4
        assert d["all-reduce"] == 7 * 16 * 128 * 4

    def test_group_sizes(self):
        g = collective_group_sizes(SYNTH)
        assert g["all-reduce"] == 4.0
        assert g["all-gather"] == 4.0

    def test_split(self):
        comps, entry = split_computations(SYNTH)
        assert entry == "main"
        assert {"add.clone", "body", "cond", "main"} <= set(comps)


class TestHbmBytes:
    def test_counts_real_ops_skips_free(self):
        b = hbm_bytes(SYNTH)
        # entry: ag (out 32768 + in 8192) + slice (8192+32768) + while body
        # 7x (ar: 8192+8192); tuples/gte/params free
        expected = (32768 + 8192) + (8192 + 32768) + 7 * (8192 + 8192)
        assert b == expected


class TestQuadraticTraffic:
    def test_detects_score_tensors(self):
        hlo = """\
ENTRY %main (a: f32[2,4096,4096]) -> f32[2,4096,4096] {
  %a = f32[2,4096,4096]{2,1,0} parameter(0)
  ROOT %e = f32[2,4096,4096]{2,1,0} exponential(%a)
}
"""
        b = quadratic_traffic(hlo, 2048, (-2, -1))
        assert b == 2 * (2 * 4096 * 4096 * 4)

    def test_ignores_thin_tensors(self):
        hlo = """\
ENTRY %main (a: f32[8192,688]) -> f32[8192,688] {
  %a = f32[8192,688]{1,0} parameter(0)
  ROOT %e = f32[8192,688]{1,0} exponential(%a)
}
"""
        assert quadratic_traffic(hlo, 2048, (-2, -1)) == 0


def test_real_compile_collectives_parse():
    """End-to-end: a psum under a 1-device mesh parses without error."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    from jax.sharding import NamedSharding, PartitionSpec as P
    with jax.set_mesh(mesh):
        f = jax.jit(lambda x: x @ x.T,
                    in_shardings=NamedSharding(mesh, P("data", "model")))
        c = f.lower(jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
    txt = c.as_text()
    assert hbm_bytes(txt) > 0
    assert isinstance(collective_bytes(txt), dict)
