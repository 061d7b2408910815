"""Signed-random-projection signatures + Hamming-threshold calibration.

For unit vectors x, y and a Gaussian direction r, ``P[sign<x,r> !=
sign<y,r>] = theta(x, y) / pi`` (Goemans–Williamson / SimHash).  With
``n_bits`` independent directions the Hamming distance between sign
signatures is Binomial(n_bits, theta/pi), so an eps-ball in cosine
distance maps to a Hamming band around ``n_bits * arccos(1-eps) / pi``
whose width shrinks like ``sqrt(n_bits)``.  That concentration is what
the ``random_projection`` backend and the ``hamming_filter`` kernel
exploit.

Signatures are packed 32 bits per uint32 word with the same bit order as
:func:`repro.core.range_query.pack_bitmap` (bit j of word w = bit
``32*w + j``), here as a jit'd jnp pipeline so projection + packing is
one fused device pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as _metrics, span as _span

__all__ = [
    "make_projection",
    "upload",
    "pack_bits",
    "unpack_bits",
    "sign_signatures",
    "shard_signatures",
    "collision_fraction",
    "hamming_band",
    "band_hits",
    "hamming_words",
    "hamming_numpy",
]


def make_projection(d: int, n_bits: int, seed: int = 0) -> np.ndarray:
    """(d, n_bits) float32 Gaussian projection; n_bits % 32 == 0."""
    if n_bits % 32 != 0:
        raise ValueError(f"n_bits must be a multiple of 32, got {n_bits}")
    rng = np.random.default_rng(seed)
    return rng.standard_normal((d, n_bits)).astype(np.float32)


def pack_bits(bits: jax.Array) -> jax.Array:
    """(n, n_bits) bool -> (n, n_bits // 32) packed uint32 (traceable;
    the single definition of the signature bit order — kernel, backend,
    and launch lowering all pack through here)."""
    n, nb = bits.shape
    words = bits.reshape(n, nb // 32, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(words << shifts[None, None, :], axis=2, dtype=jnp.uint32)


def unpack_bits(words: jax.Array, n_bits: int) -> jax.Array:
    """(n, n_words) packed uint32 -> (n, n_bits) bool (traceable inverse
    of :func:`pack_bits`; same LSB-first bit order)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    return bits.reshape(words.shape[0], -1)[:, :n_bits].astype(bool)


def band_hits(dots, ham, eps, t_lo, t_hi):
    """The unified band predicate shared by every execution path.

    hit  <=>  ham <= t_lo  (sure-accept, no exact verify)
           or (ham <= t_hi and dot > 1 - eps)  (band, exact-verified).

    ``t_lo = -1`` is full-verify mode (no sure-accepts).  Works on numpy
    and jnp operands alike — the host backend, the kernel oracle, and
    the sharded lowering all evaluate this one definition.
    """
    return (ham <= t_lo) | ((ham <= t_hi) & (dots > 1.0 - eps))


def hamming_words(a: jax.Array, b: jax.Array) -> jax.Array:
    """(na, nb) int32 Hamming distances between packed signature rows
    (traceable; static unrolled word loop, XOR + popcount per word —
    usable inside jit and inside Pallas kernels)."""
    ham = jnp.zeros((a.shape[0], b.shape[0]), jnp.int32)
    for k in range(a.shape[1]):
        x = a[:, k][:, None] ^ b[:, k][None, :]
        ham = ham + jax.lax.population_count(x).astype(jnp.int32)
    return ham


def upload(host: np.ndarray, what: str, *, pad_rows: int = 0,
           sharding=None) -> jax.Array:
    """Copy one database-sized operand to the device, zero-padded by
    ``pad_rows`` rows first (the padded host copy is built inside).
    ``sharding`` places it sharded instead: the copy lands whole on the
    default device and is padded and split across the devices there (a
    host array put straight to a sharding crossed at about a tenth of
    the rate on a four-chip v5e host).

    Each copy is one ``laf.upload`` span, synced on the device array so
    that with tracing on it times the transfer, not the enqueue, and
    adds its ``nbytes`` to the ``index.upload.bytes`` counter."""
    with _span("laf.upload", what=what) as sp:
        if sharding is not None:
            dev = jnp.asarray(host)
            if pad_rows:
                dev = jnp.pad(dev, ((0, pad_rows),) + ((0, 0),) * (dev.ndim - 1))
            dev = jax.device_put(dev, sharding)
            shape, nbytes = dev.shape, dev.nbytes
        else:
            if pad_rows:
                buf = np.zeros((host.shape[0] + pad_rows,) + host.shape[1:], host.dtype)
                buf[: host.shape[0]] = host
                host = buf
            dev = jnp.asarray(host)
            shape, nbytes = host.shape, host.nbytes
        sp.set(rows=int(shape[0]), bytes=int(nbytes)).sync_on(dev)
    _metrics.counter("index.upload.bytes").inc(int(nbytes))
    return dev


@jax.jit
def _sign_pack(data: jax.Array, proj: jax.Array) -> jax.Array:
    return pack_bits((data @ proj) >= 0.0)


def sign_signatures(data: np.ndarray, proj: np.ndarray) -> np.ndarray:
    """Packed (n, n_bits // 32) uint32 sign signatures of ``data @ proj``."""
    corpus = upload(np.asarray(data, np.float32), "corpus")
    return np.asarray(_sign_pack(corpus, jnp.asarray(proj)))


def shard_signatures(mesh, sigs, spec=None, *, n_padded: int | None = None):
    """Place a packed signature table co-sharded with the database rows
    it summarizes, through one ``laf.upload`` span
    (``what="shard_sigs"``).

    ``spec`` defaults to ``P(data_axes(mesh), None)`` — rows over the
    mesh's data axes, words replicated — the one layout the index plane
    (``repro.distributed.index_plane``) accepts; pass an explicit
    ``PartitionSpec`` to shard over other axes.  ``n_padded`` zero-pads
    the row axis first (plane plans require a shard multiple; zero
    signature words are exactly what the kernel wrappers' padded-row
    correction models).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..distributed.sharding import data_axes

    sigs = np.asarray(sigs, np.uint32)
    pad = 0 if n_padded is None else max(n_padded - sigs.shape[0], 0)
    if spec is None:
        spec = P(data_axes(mesh), None)
    return upload(sigs, "shard_sigs", pad_rows=pad, sharding=NamedSharding(mesh, spec))


def collision_fraction(eps: float) -> float:
    """Expected differing-bit fraction for a pair at cosine distance eps."""
    return math.acos(float(np.clip(1.0 - eps, -1.0, 1.0))) / math.pi


def hamming_band(eps: float, n_bits: int, margin: float = 3.0) -> tuple[int, int]:
    """(t_lo, t_hi) Hamming thresholds for an eps-ball at ``margin`` sigmas.

    Pairs with distance <= t_lo are (with prob ~Phi(margin)) inside the
    ball; pairs with distance > t_hi are outside; the band in between is
    where exact verification is required.  t_lo < 0 means "no sure
    accepts" (small n_bits or eps near 0).
    """
    p = collision_fraction(eps)
    sd = math.sqrt(max(p * (1.0 - p), 1e-12) / n_bits)
    t_hi = min(n_bits, int(math.ceil(n_bits * (p + margin * sd))))
    t_lo = int(math.floor(n_bits * (p - margin * sd)))
    return t_lo, t_hi


_POPCOUNT8 = np.array([bin(i).count("1") for i in range(256)], dtype=np.uint8)


def hamming_numpy(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(na, nb) Hamming distances between packed uint32 signature rows.

    Host-side path for small column subsets (the jit'd popcount pass in
    the backend covers full-database sweeps).
    """
    a = np.ascontiguousarray(a, dtype=np.uint32)
    b = np.ascontiguousarray(b, dtype=np.uint32)
    x = np.ascontiguousarray(a[:, None, :] ^ b[None, :, :])  # (na, nb, w)
    if hasattr(np, "bitwise_count"):  # numpy >= 2.0
        per_word = np.bitwise_count(x)
    else:
        per_word = _POPCOUNT8[x.view(np.uint8)].reshape(*x.shape[:2], -1)
    return per_word.sum(axis=-1, dtype=np.int32)
