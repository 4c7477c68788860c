"""Fixture: device-mesh purity violations (MSH13xx)."""

import time

import numpy as np
from jax import shard_map


class Runner:
    def build(self, mesh):
        def _local(x):
            t0 = time.perf_counter()  # MSH1301: host call under tracing
            y = np.asarray(x)  # MSH1301: numpy is host work
            self.last = t0  # MSH1302: host state write in traced body
            return y

        return shard_map(_local, mesh=mesh, in_specs=None, out_specs=None)

    def build_global(self, mesh):
        def _g(x):
            global _count  # MSH1302: global mutation under tracing
            _count += 1
            return _helper(x)

        return shard_map(_g, mesh=mesh, in_specs=None, out_specs=None)


def _helper(x):
    # mesh membership propagates through resolved calls: this helper is
    # only reached from a shard_map-traced body, so its print is flagged
    print("tracing", x)  # MSH1301: host builtin
    return x


def clean(mesh):
    import jax.numpy as jnp

    def _local(x):
        return jnp.sum(x)  # fine: device-side work only

    return shard_map(_local, mesh=mesh, in_specs=None, out_specs=None)
