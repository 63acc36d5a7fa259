"""Unit tests for the HLO collective-bytes parser and roofline helpers."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_stats import _shape_bytes, collective_bytes


def test_shape_bytes():
    assert _shape_bytes("bf16[8,128]{1,0}") == 8 * 128 * 2
    assert _shape_bytes("f32[100]") == 400
    assert _shape_bytes("(f32[4], s8[16])") == 16 + 16
    assert _shape_bytes("pred[]") == 1
    assert _shape_bytes("token[]") == 0


def test_collective_parse_synthetic():
    hlo = """
HloModule m
  %ar = bf16[1024,8]{1,0} all-reduce(%x), replica_groups={}
  %ag = f32[64]{0} all-gather(%y), dimensions={0}
  %rs = f32[32]{0} reduce-scatter(%z), dimensions={0}
  %a2a = (s8[16], s8[16]) all-to-all(%p, %q)
  %cp = u32[128]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %cps = u32[128]{0} collective-permute-start(%w)
  %add = f32[2] add(%a, %b)
"""
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 1024 * 8 * 2
    assert out["all-gather"] == 256
    assert out["reduce-scatter"] == 128
    assert out["all-to-all"] == 32
    # -start counted once, plain counted once
    assert out["collective-permute"] == 2 * 128 * 4
    assert out["_counts"]["all-reduce"] == 1


def test_collective_parse_real_program():
    """psum under shard_map must show up as all-reduce bytes."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))

    def f(a):
        return jax.lax.psum(a, "x")

    fn = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(),), out_specs=P()))
    lowered = fn.lower(jax.ShapeDtypeStruct((256,), jnp.float32))
    text = lowered.compile().as_text()
    out = collective_bytes(text)
    assert out.get("all-reduce", 0) >= 256 * 4


def test_param_count_sanity():
    from benchmarks.roofline import _param_count
    from repro.configs import get_config

    n, a = _param_count(get_config("deepseek-7b"))
    assert 6e9 < n < 8.5e9 and a == n
    n, a = _param_count(get_config("qwen1.5-32b"))
    assert 28e9 < n < 37e9
    n, a = _param_count(get_config("dbrx-132b"))
    assert 110e9 < n < 145e9
    assert 25e9 < a < 45e9  # top-4 of 16 experts + attention
    n, a = _param_count(get_config("phi3.5-moe-42b-a6.6b"))
    assert 38e9 < n < 46e9
    assert 5e9 < a < 9e9
    n, a = _param_count(get_config("rwkv6-1.6b"))
    assert 1.2e9 < n < 2.2e9
