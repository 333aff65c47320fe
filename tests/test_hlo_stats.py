"""Collective-bytes parser: synthetic HLO lines + a real lowered module."""
import jax
import jax.numpy as jnp

from repro.launch.hlo_stats import collective_stats

SYNTH = """
ENTRY %main {
  %p0 = bf16[2,128]{1,0} parameter(0)
  %ag = bf16[4,128]{1,0} all-gather(bf16[2,128]{1,0} %p0), replica_groups={}
  %ar = f32[64]{0} all-reduce(f32[64]{0} %x), to_apply=%add
  %rs = f32[16]{0} reduce-scatter(f32[64]{0} %x), dimensions={0}
  %cp = u8[10]{0} collective-permute(u8[10]{0} %y), source_target_pairs={{0,1}}
  %aa-start = f32[8,8]{1,0} all-to-all-start(f32[8,8]{1,0} %z)
  %aa-done = f32[8,8]{1,0} all-to-all-done(f32[8,8]{1,0} %aa-start)
}
"""


def test_synthetic_counts():
    st = collective_stats(SYNTH)
    assert st.count_by_kind["all-gather"] == 1
    assert st.bytes_by_kind["all-gather"] == 2 * 128 * 2
    assert st.bytes_by_kind["all-reduce"] == 64 * 4
    assert st.bytes_by_kind["reduce-scatter"] == 64 * 4
    assert st.bytes_by_kind["collective-permute"] == 10
    # -start counted once, -done skipped
    assert st.count_by_kind["all-to-all"] == 1


def test_real_lowered_psum():
    """An actual jax collective must be found in the compiled HLO."""
    mesh = jax.make_mesh((1,), ("x",))
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    def f(a):
        return jax.lax.psum(a, "x")

    fn = shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P())
    lowered = jax.jit(fn).lower(jnp.ones((8, 4), jnp.float32))
    txt = lowered.compile().as_text()
    st = collective_stats(txt)
    # single-device meshes may fold the psum away; at minimum the parser
    # must not crash and must return a well-formed result
    assert st.total_bytes >= 0
    assert set(st.bytes_by_kind) == {
        "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
        "collective-permute"}
