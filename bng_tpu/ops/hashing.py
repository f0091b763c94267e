"""Hash mixing shared bit-for-bit between host (numpy) and device (jax.numpy).

The reference hashes keys with FNV-1a (pkg/ebpf/loader.go:546-553,
pkg/nexus/client.go:694) and relies on the kernel's htab hashing for eBPF
maps. Here the host is the single writer of device tables, so the host-side
(numpy) and device-side (jnp) hash of a key MUST agree exactly; both call
these functions, which only use uint32 ops with identical wrapping semantics
under numpy>=2 weak promotion and jax.

The mixer is the public-domain "lowbias32" integer finalizer; two different
seeds give the two independent hash functions cuckoo hashing needs.
"""

from __future__ import annotations

import numpy as np

# Two independent seeds for the cuckoo table's two hash functions.
# np.uint32-wrapped: jax refuses python ints above int32 max next to uint32
# arrays, and numpy scalars would raise on overflow; uint32 scalars wrap
# identically on both sides.
SEED1 = np.uint32(0x9E3779B9)
SEED2 = np.uint32(0x85EBCA6B)

_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)


def mix32(h):
    """lowbias32 avalanche mixer. Works on numpy or jnp uint32 arrays."""
    h = h ^ (h >> 16)
    h = h * _M1
    h = h ^ (h >> 15)
    h = h * _M2
    h = h ^ (h >> 16)
    return h


def hash_words(words, seed):
    """Hash a sequence of uint32 word arrays into one uint32 array.

    `words` is a list of arrays (all the same shape); the hash is order
    dependent. Equivalent role to FNV-1a over the key bytes in the
    reference, but word-wide for TPU vector units.
    """
    h = words[0] ^ seed
    h = mix32(h)
    for w in words[1:]:
        h = mix32(h ^ w)
    return h


def hash_words_int(words, seed: int) -> int:
    """`hash_words` of ONE key given as Python ints: the same 32-bit
    arithmetic, masked by hand, with no array in it. The host tables hash
    a single key some seven times an insert (ops/table.py HostTable), and
    one-element numpy arrays cost a microsecond an operation: 65 us a hash
    against 2 (tests/test_newflow_forwarding.py holds the two equal bit for
    bit)."""
    m1, m2, mask = int(_M1), int(_M2), 0xFFFFFFFF
    h = None
    for w in words:
        h = (w ^ int(seed)) if h is None else (h ^ w)
        h ^= h >> 16
        h = (h * m1) & mask
        h ^= h >> 15
        h = (h * m2) & mask
        h ^= h >> 16
    return h
