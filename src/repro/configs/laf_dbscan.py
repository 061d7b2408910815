"""laf_dbscan: the paper's own workload as a first-class config — the
distributed clustering step (sharded range counting + RMI estimation)
lowered on the production mesh alongside the assigned architectures.

Dataset operating points follow the paper's Table 1 (n, d); the dry-run
lowers ``cluster_step`` = one frontier round: batched RMI prediction for
the frontier + fused range counting of predicted-core queries against
the device-sharded database + one label-propagation round.
"""

from dataclasses import dataclass
from typing import Mapping

import jax.numpy as jnp

from .registry import ArchSpec, ShapeSpec, register


@dataclass(frozen=True)
class StreamConfig:
    """Knobs for the streaming subsystem (``repro.stream``).

    ``alpha`` is the online analog of the paper's skip factor: a new
    point whose predicted cardinality is below ``alpha * tau`` skips its
    full range query at ingest (it is verified against the core set
    only, and promoted later if its partial count crosses tau).
    ``use_estimator=False`` disables the skip entirely — every ingested
    row pays one range query, which is the exact (parity) mode.
    """

    batch_rows: int = 4096      # driver-side ingest chunking
    use_estimator: bool = False  # RMI predict-core fast path at ingest
    alpha: float = 1.0           # online skip factor (pred < alpha*tau skips)
    shortlist: int = 8           # serve: centroid clusters expanded per query
    min_hits: int = 1            # serve: eps-neighbors required to assign
    max_dead_frac: float = 0.25  # eviction: tombstone fraction forcing rebuild
    snapshot_every: int = 8      # durability: WAL batches between snapshots


@dataclass(frozen=True)
class LAFClusterConfig:
    n_points: int
    dim: int
    eps: float = 0.55
    tau: int = 5
    alpha: float = 1.5
    frontier: int = 4096      # queries per frontier round
    dtype: object = jnp.float32
    # range-query backend (repro.index): "exact" = brute-force matmul,
    # "random_projection" = sign-signature Hamming prefilter + verify
    # (kernels.hamming_filter on device); index_bits sizes the signature,
    # index_seed fixes the projection (db signatures MUST be packed with
    # the same seed/bits), index_margin sets the Hamming band width.
    # index_verify picks the backend's dual-threshold semantics
    # ("band" = sure-accept below t_lo + exact-verify the band; "full" =
    # t_lo disabled, every candidate verified).  index_device routes the
    # frontier round through the fused hamming_filter Pallas tile
    # (True | False | "auto") on ANY mesh size: multi-device meshes run
    # the tile shard-locally via the index plane
    # (repro.distributed.index_plane), with the packed signature table
    # co-sharded with the database rows; "auto" = the tile on every
    # multi-device mesh and on TPU-backed single devices (a
    # lone CPU device keeps the shardable jnp dataflow of the same
    # predicate).  index_axes names the mesh axes the db rows +
    # signature table are co-sharded over ("auto" = every mesh axis).
    # index_pipeline sets the frontier sweep's software-pipeline depth
    # through the sharded plane: 2 (default) double-buffers chunks so
    # chunk k's cross-shard count psum overlaps chunk k+1's shard-local
    # popcount+verify; 1 serializes them (the parity baseline).
    backend: str = "exact"
    index_bits: int = 512
    index_seed: int = 0
    index_margin: float = 3.0
    index_verify: str = "band"
    index_device: object = "auto"
    index_axes: object = "auto"
    index_pipeline: int = 2
    # cluster_device routes cluster *formation* (tau core test +
    # core-graph components + border rule): "auto" follows the backend
    # — when it packs adjacency on device (packs_natively) the sweep's
    # bitmap slab feeds the packed label-propagation while_loop program
    # and the whole clustering syncs to the host exactly once (final
    # labels); True forces the device program even for host backends
    # (packed blocks uploaded once — the parity mode); False forces the
    # host unpack -> union-find pass (the parity oracle).
    cluster_device: object = "auto"
    # device-resident telemetry (repro.obs.device): "auto" follows the
    # process-wide switch (obs.enable(telemetry=True) / REPRO_OBS=device)
    # at build time; True/False pin it per config.  When on, the fused
    # loops carry small s32 counter vectors (per-round frontier/changed/
    # hops/shard-wins in the cluster fixpoint, per-chunk accept/band/
    # reject in the sweep) harvested at the existing single device_get.
    telemetry: object = "auto"
    # streaming subsystem (repro.stream): online ingest + serving knobs
    stream: StreamConfig = StreamConfig()


def make_config():
    # MS-150k operating point (paper Table 1: 152,185 x 768)
    return LAFClusterConfig(n_points=152185, dim=768)


def make_reduced_config():
    return LAFClusterConfig(n_points=2048, dim=64, frontier=256, index_bits=128)


LAF_SHAPES: Mapping[str, ShapeSpec] = {
    "nyt_150k": ShapeSpec("nyt_150k", "cluster", {"n_points": 150000, "dim": 256}),
    "glove_150k": ShapeSpec("glove_150k", "cluster", {"n_points": 150000, "dim": 200}),
    "ms_150k": ShapeSpec("ms_150k", "cluster", {"n_points": 152185, "dim": 768}),
    "web_1b": ShapeSpec("web_1b", "cluster", {"n_points": 1_073_741_824, "dim": 768}),
}

SPEC = register(
    ArchSpec(
        name="laf_dbscan",
        family="cluster",
        make_config=make_config,
        make_reduced_config=make_reduced_config,
        shapes=LAF_SHAPES,
        notes="the paper's technique itself; web_1b is the 1000+-node scale target",
    )
)
