"""Fixture: every experimental shard_map import/use form QBS001 must
catch, beside the jax.shard_map forms it must accept."""
import jax
import jax.experimental.shard_map                         # QBS001
from jax.experimental.shard_map import shard_map          # QBS001
from jax.experimental import shard_map as sm              # QBS001
from jax import shard_map as jsm                          # ok


def f(fn, mesh):
    return jax.experimental.shard_map.shard_map(fn, mesh=mesh)   # QBS001


def g(fn):
    return jax.shard_map(fn)                              # ok


__all__ = ["f", "g", "shard_map", "sm", "jsm"]
