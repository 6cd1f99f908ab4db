import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P
import numpy as np

from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
axes = ("data", "model")
WORLD = 8


def f(x):
    # x: [1, cap] per device after sharding [8, cap]
    idx = lax.axis_index(axes)
    send = jnp.tile(idx * 100 + jnp.arange(WORLD)[:, None], (1, 1)).astype(jnp.int32)  # [8,1] msg to each peer
    recv = lax.all_to_all(send, axes, split_axis=0, concat_axis=0, tiled=True)
    return recv.reshape(1, WORLD), idx.reshape(1, 1)


xs = jnp.zeros((WORLD, 4), jnp.int32)
recv, idxs = jax.jit(shard_map(f, mesh=mesh, in_specs=P(axes), out_specs=P(axes)))(xs)
print("axis_index per device:", np.array(idxs).ravel())
print("recv on device 0:", np.array(recv)[0])   # expect [0,100,200,...,700] + 0
print("recv on device 3:", np.array(recv)[3])   # expect j*100+3

# block sharding order: does P(('data','model')) block k go to axis_index k?
w = jnp.arange(WORLD * 2).reshape(WORLD * 2, 1)


def g(wshard):
    idx = lax.axis_index(axes)
    return (wshard[0] == idx * 2).reshape(1, 1)


ok = jax.jit(shard_map(g, mesh=mesh, in_specs=P(axes), out_specs=P(axes)))(w)
print("block order matches axis_index:", np.array(ok).ravel())

# all_gather + psum with tuple axes
def h(x):
    g = lax.all_gather(x, axes, tiled=True)
    s = lax.psum(x.sum(), axes)
    return g.reshape(1, -1), s.reshape(1, 1)


gg, ss = jax.jit(shard_map(h, mesh=mesh, in_specs=P(axes), out_specs=(P(axes), P(axes))))(
    jnp.arange(8.0).reshape(8, 1))
print("all_gather row0:", np.array(gg)[0], "psum:", np.array(ss).ravel()[0])
