"""Scratch probe: compare u128 limb-matmul inner-loop variants on TPU.

Variants (single (n,n) x (n,n) u128 contraction, 16 centered int8 limbs):
  pairs     per-pair dot_generals, s32 diagonal accumulation (the r3 path)
  slab      one dot_general per diagonal over concat slices (unpadded)
  slab_pad  same, with k padded to a multiple of 512 so slices are aligned
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import moose_tpu  # noqa: F401
import jax
import jax.numpy as jnp

n = int(sys.argv[1]) if len(sys.argv) > 1 else 1000
rng = np.random.default_rng(0)
a = rng.integers(0, 1 << 64, size=(n, n), dtype=np.uint64)
b = rng.integers(0, 1 << 64, size=(n, n), dtype=np.uint64)


def limbs(x):
    return [
        (((x >> np.uint64(8 * i)) & np.uint64(0xFF)).astype(jnp.int32) - 128)
        .astype(jnp.int8)
        for i in range(8)
    ]


def diags_pairs(la, lb, k):
    ra = [jnp.sum(x.astype(jnp.int32), axis=-1) for x in la]
    cb = [jnp.sum(x.astype(jnp.int32), axis=0) for x in lb]
    L = len(la)
    out = []
    for s in range(L):
        ps = None
        for i in range(min(s + 1, L)):
            j = s - i
            p = jax.lax.dot_general(
                la[i], lb[j], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            p = p + (
                jnp.int32(128) * (ra[i][:, None] + cb[j][None, :])
                + jnp.int32(128 * 128 * k)
            )
            ps = p if ps is None else ps + p
        out.append(ps.astype(jnp.int64).astype(jnp.uint64))
    return out


def diags_slab(la, lb, k, pad_to=0):
    ra = [jnp.sum(x.astype(jnp.int32), axis=-1) for x in la]
    cb = [jnp.sum(x.astype(jnp.int32), axis=0) for x in lb]
    L = len(la)
    kp = k if not pad_to else -(-k // pad_to) * pad_to
    if kp != k:
        la = [jnp.pad(x, ((0, 0), (0, kp - k))) for x in la]
        lb = [jnp.pad(x, ((0, kp - k), (0, 0))) for x in lb]
    afull = jnp.concatenate(la, axis=-1)
    brev = jnp.concatenate(lb[::-1], axis=0)
    out = []
    for s in range(L):
        i0, i1 = max(0, s - (L - 1)), min(s, L - 1)
        npairs = i1 - i0 + 1
        a_sl = afull[:, i0 * kp:(i1 + 1) * kp]
        b0 = (L - 1 - s + i0) * kp
        b_sl = brev[b0:b0 + npairs * kp, :]
        ps = jax.lax.dot_general(
            a_sl, b_sl, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        tra = sum(ra[i] for i in range(i0, i1 + 1))
        tcb = sum(cb[s - i] for i in range(i0, i1 + 1))
        ps = ps + (
            jnp.int32(128) * (tra[:, None] + tcb[None, :])
            + jnp.int32(128 * 128 * k * npairs)
        )
        out.append(ps.astype(jnp.int64).astype(jnp.uint64))
    return out


def recombine(diags):
    acc = jnp.zeros_like(diags[0])
    for s, d in enumerate(diags):
        acc = acc + (d << np.uint64(8 * s))
    return acc


da, db = None, None


def run(name, fn):
    global da, db
    if da is None:
        da, db = jax.device_put(a), jax.device_put(b)
    f = jax.jit(fn)
    out = jax.block_until_ready(f(da, db))
    ref = (a.astype(object) @ b.astype(object)) % (1 << 64) if n <= 256 else None
    if ref is not None:
        assert np.array_equal(np.asarray(out), ref.astype(np.uint64)), name
    g = jax.jit(lambda x, y: jnp.sum(fn(x, y)))
    float(g(da, db))  # compile + warm
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(50):
            s = g(da, db)
        float(s)  # scalar readback forces true execution
        times.append((time.perf_counter() - t0) / 50)
    print(f"{name}: {min(times)*1e3:.3f} ms")


run("pairs    ", lambda x, y: recombine(diags_pairs(limbs(x), limbs(y), n)))
run("slab     ", lambda x, y: recombine(diags_slab(limbs(x), limbs(y), n)))
run("slab_512 ", lambda x, y: recombine(diags_slab(limbs(x), limbs(y), n, 512)))
run("slab_128 ", lambda x, y: recombine(diags_slab(limbs(x), limbs(y), n, 128)))
