"""Collective-bytes parser on hand-built HLO fragments + a real lowering."""
import jax
import jax.numpy as jnp

from repro.launch import hlo_analysis as H


def test_shape_bytes():
    assert H.shape_bytes("f32[8,128]{1,0}") == 8 * 128 * 4
    assert H.shape_bytes("bf16[4096]") == 8192
    assert H.shape_bytes("(f32[2,2], bf16[4])") == 16 + 8
    assert H.shape_bytes("pred[]") == 1
    assert H.shape_bytes("token[]") == 0


SYNTH = """\
HloModule synth, num_partitions=4

%body.1 (p: (s32[], f32[16])) -> (s32[], f32[16]) {
  %ar = f32[16]{0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add
}

%cond.1 (p: (s32[], f32[16])) -> pred[] {
  %c = s32[] constant(7)
  %cmp = pred[] compare(%i, %c), direction=LT
}

ENTRY %main (a: f32[16]) -> f32[16] {
  %ag = f32[64]{0} all-gather(%a), replica_groups={{0,1,2,3}}, dimensions={0}
  %w = (s32[], f32[16]) while(%t), condition=%cond.1, body=%body.1
  %cp = f32[16]{0} collective-permute(%a), source_target_pairs={{0,1},{1,0}}
}
"""


def test_synthetic_module_weighted_counts():
    rep = H.collective_report(SYNTH, total_devices=4)
    assert rep.counts == {"all-reduce": 1, "all-gather": 1,
                          "collective-permute": 1}
    # all-gather: (n-1)/n * 64*4 = 192 ; permute: 64 bytes
    # all-reduce in the loop: 2*(3/4)*64 = 96, weighted by trip 7 -> 672
    assert rep.flat_bytes == 192 + 64 + 96
    assert rep.weighted_bytes == 192 + 64 + 96 * 7
    assert rep.weighted_counts["all-reduce"] == 7.0


def test_known_trip_count_preferred():
    mod = SYNTH.replace(
        "condition=%cond.1, body=%body.1",
        'condition=%cond.1, body=%body.1, '
        'backend_config={"known_trip_count":{"n":"13"}}')
    rep = H.collective_report(mod, total_devices=4)
    assert rep.weighted_counts["all-reduce"] == 13.0


def test_iota_replica_groups():
    mod = SYNTH.replace("replica_groups={{0,1,2,3}}, dimensions={0}",
                        "replica_groups=[2,2]<=[4]T(1,0), dimensions={0}")
    rep = H.collective_report(mod, total_devices=4)
    # all-gather group size n=2: (1/2)*256 = 128
    assert rep.by_comp["main"] >= 128


def test_real_lowering_collectives():
    """A psum under shard_map on a 1-device mesh lowers; the parser runs on
    real HLO without crashing (byte count may be 0 on 1 device)."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("x",))
    f = jax.shard_map(lambda x: jax.lax.psum(x, "x"), mesh=mesh,
                  in_specs=P("x"), out_specs=P())
    hlo = jax.jit(f).lower(jnp.ones((4, 4))).compile().as_text()
    rep = H.collective_report(hlo, total_devices=1)
    assert rep.flat_bytes >= 0
