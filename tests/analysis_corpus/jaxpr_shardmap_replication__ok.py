"""Corpus OK twin: the shard-local sum is psum'd over the mesh axis
before being declared replicated.

Imported and executed by the corpus runner via build().
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def build():
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))

    def global_sum(x):
        return jax.lax.psum(jnp.sum(x), "data")

    f = jax.shard_map(
        global_sum, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
        check_vma=False,
    )
    return {"jaxpr": jax.make_jaxpr(f)(jnp.zeros((8,), jnp.float32))}
