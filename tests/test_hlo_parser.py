"""Collective-traffic HLO parser, and the op -> named-scope map."""

import jax
import jax.numpy as jnp

from repro.utils.hlo import collective_bytes_from_hlo, op_scopes


HLO = """
HloModule test
%all-reduce.216 = f32[4,512,2048]{2,1,0} all-reduce(%fusion.5), channel_id=1, replica_groups=[8,8]<=[64], use_global_device_ids=true, to_apply=%add
%ag = bf16[64,128]{1,0} all-gather(%p0), channel_id=2, replica_groups=[4,4]<=[16], dimensions={0}
%rs = f32[16,128]{1,0} reduce-scatter(%p1), channel_id=3, replica_groups=[2,8]<=[16], to_apply=%add
%cp = f32[32]{0} collective-permute(%p2), source_target_pairs={{0,1},{1,0}}
%ard = f32[4]{0} all-reduce-done(%h)
%tuple_ar = (f32[128]{0}, f32[128]{0}) all-reduce(%a, %b), replica_groups=[1,4]<=[4], to_apply=%add
"""


def test_parses_ops_and_bytes():
    s = collective_bytes_from_hlo(HLO)
    # all-reduce: 4*512*2048*4 + tuple 2*128*4; -done excluded
    ar = 4 * 512 * 2048 * 4 + 2 * 128 * 4
    assert s.bytes_by_op["all-reduce"] == ar
    assert s.count_by_op["all-reduce"] == 2
    # all-gather operand = output / group(4)
    assert s.bytes_by_op["all-gather"] == 64 * 128 * 2 / 4
    # reduce-scatter operand = output * group(8)
    assert s.bytes_by_op["reduce-scatter"] == 16 * 128 * 4 * 8
    assert s.bytes_by_op["collective-permute"] == 32 * 4
    assert "all-reduce-done" not in " ".join(s.bytes_by_op)


def test_wire_model_is_ring():
    s = collective_bytes_from_hlo(HLO)
    # all-gather wire = (g-1)/g * full
    assert abs(s.wire_bytes_by_op["all-gather"] - 64 * 128 * 2 * 3 / 4) < 1e-6


def test_replica_group_list_form():
    s = collective_bytes_from_hlo(
        "%x = f32[8]{0} all-gather(%p), replica_groups={{0,1,2,3}}, dimensions={0}")
    assert s.bytes_by_op["all-gather"] == 8 * 4 / 4


def test_op_scopes_maps_ops_that_run():
    def f(src, r):
        def body(i, r):
            with jax.named_scope("demo.gather"):
                w = r[src] * 2.0
            with jax.named_scope("demo.scatter"):
                return jnp.zeros_like(r).at[src].add(w)
        return jax.lax.fori_loop(0, 3, body, r)

    text = jax.jit(f).lower(jnp.arange(32) % 8, jnp.ones(8)).compile().as_text()
    scopes = op_scopes(text)
    paths = [s.split("/") for s in scopes.values()]
    assert any("demo.gather" in p for p in paths) and any("demo.scatter" in p for p in paths)
    # control flow and ops that do no work are left out
    for line in text.splitlines():
        if any(f" {op}(" in line for op in ("while", "parameter", "constant")):
            assert line.split("=")[0].split()[-1].lstrip("%") not in scopes, line


def test_op_scopes_by_hand():
    text = """HloModule demo

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/a/mul"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion = f32[4]{0:T(1024)} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/a/mul"}
  %while.2 = (s32[], f32[4]{0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(f)/while"}
  ROOT %sort.3 = f32[4]{0} sort(%fusion), dimensions={0}, to_apply=%cmp, metadata={op_name="jit(f)/b/sort"}
}
"""
    assert op_scopes(text) == {"fusion": "jit(f)/a/mul", "sort.3": "jit(f)/b/sort"}
