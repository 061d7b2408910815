"""Corpus BAD: shard_map declares a replicated output (out_specs=P())
but never reduces over the mesh axis — shard-local partial sums
masquerade as a replicated value (correct on 1 device, wrong on N).

Imported and executed by the corpus runner via build().
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def build():
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))

    def local_sum(x):
        return jnp.sum(x)  # no psum over "data"

    f = jax.shard_map(
        local_sum, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
        check_vma=False,
    )
    return {"jaxpr": jax.make_jaxpr(f)(jnp.zeros((8,), jnp.float32))}
