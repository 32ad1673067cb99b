"""Everything the benchmark draws from ``--seed``, as counter-based hashes.

A value is a pure function of (seed, stream tag, coordinates), so the
program's tables can be filled on the device in one jitted call while the
plain reference evaluates the same function for just the rows it touches.
Every function takes ``xp`` — ``numpy`` or ``jax.numpy`` — and does 32-bit
unsigned arithmetic only, so both give the same bits with x64 off.
"""

import numpy as np

MASK62 = np.uint64((1 << 62) - 1)

# stream tags: one per kind of seeded value
TAG_TABLE = 0x7461626C        # embedding rows, by (table, feature, id)
TAG_DENSE = 0x64656E73        # dense parameters, by (leaf, element)


def fmix32(h, xp=np):
    """murmur3's 32-bit finalizer."""
    u = xp.uint32
    h = h ^ (h >> u(16))
    h = h * u(0x85EBCA6B)
    h = h ^ (h >> u(13))
    h = h * u(0xC2B2AE35)
    return h ^ (h >> u(16))


def seed_word(seed):
    """The seed folded to 32 bits. A jitted fill takes it as an argument,
    so that a new seed runs the program the last one compiled."""
    seed = int(seed)
    return np.uint32((seed ^ (seed >> 32)) & 0xFFFFFFFF)


def hash_u32(seed, tag, words, xp=np):
    """One uint32 per element from the seed (a whole number, or its
    :func:`seed_word` as an array), a stream tag and any number of
    broadcastable uint32 coordinate arrays."""
    u = xp.uint32
    if isinstance(seed, int):
        seed = seed_word(seed)
    with np.errstate(over="ignore"):      # numpy scalars warn on wrap-around
        h = fmix32(xp.asarray(seed, dtype=xp.uint32) ^ u(tag), xp)
        for k, w in enumerate(words):
            h = fmix32((h ^ xp.asarray(w).astype(xp.uint32))
                       + u((0x9E3779B9 * (k + 1)) & 0xFFFFFFFF), xp)
    return h


def uniform(seed, tag, words, scale, xp=np):
    """float32 uniform on [-scale, scale) from :func:`hash_u32`."""
    h = hash_u32(seed, tag, words, xp)
    unit = (h >> xp.uint32(8)).astype(xp.float32) * xp.float32(2.0 ** -24)
    return (unit * xp.float32(2.0) - xp.float32(1.0)) * xp.float32(scale)


def table_rows(seed, table, feature, id_lo, id_hi, dim, scale, xp=np):
    """Initial rows ``[..., dim]`` of embedding table number ``table`` for
    (feature, id) pairs; a 64-bit id comes as its low and high words."""
    col = xp.arange(dim, dtype=xp.uint32)
    f = xp.asarray(feature).astype(xp.uint32)[..., None]
    lo = xp.asarray(id_lo).astype(xp.uint32)[..., None]
    hi = xp.asarray(id_hi).astype(xp.uint32)[..., None]
    t = xp.uint32(table)
    return uniform(seed, TAG_TABLE, (t, f, lo, hi, col), scale, xp)


def dense_leaf(seed, leaf, shape, scale, xp=np):
    """One dense parameter leaf, by leaf number and flat element index."""
    n = int(np.prod(shape))
    idx = xp.arange(n, dtype=xp.uint32)
    return uniform(seed, TAG_DENSE, (xp.uint32(leaf), idx), scale,
                   xp).reshape(shape)


def mix64(x):
    """splitmix64 finalizer on numpy uint64 (the ``to_hash_bucket_fast``
    role of the reference's Criteo-1TB reader); host side only."""
    x = x.astype(np.uint64)
    x = (x ^ (x >> np.uint64(33))) * np.uint64(0xFF51AFD7ED558CCD)
    x = (x ^ (x >> np.uint64(33))) * np.uint64(0xC4CEB9FE1A85EC53)
    return x ^ (x >> np.uint64(33))


def split_words(ids):
    """int64/uint64 ids -> (low, high) uint32 words."""
    u = np.asarray(ids).astype(np.uint64)
    return ((u & np.uint64(0xFFFFFFFF)).astype(np.uint32),
            (u >> np.uint64(32)).astype(np.uint32))
