"""By hand, on the chip (PR 33): the forest comparison's AND banks at
(128, 4150) ring128 under ``threefry``, four ways of joining the 16 banks'
draws (a seed a bank through ``jax.vmap``, which is the program's; a seed a
bank, stacked; one seed for all; a seed a bank drawn as ``uint64`` and
split), the draw alone (reduced, so that every word is made) and the draw
with the ``msb`` kernel.  Milliseconds, means of 20 after 3:
``chiprun --chips 1 -- env PYTHONPATH=. python3 scripts/bank_draw_micro.py``.
PERF.md, PR 33, has the readings (2.13 and 5.53 ms, every way)."""
import sys, time
import jax, jax.numpy as jnp, numpy as np
import moose_tpu  # noqa: F401
from moose_tpu.dialects import ring
from moose_tpu.native import ring128_kernels as rk
from moose_tpu.parallel import spmd, spmd_math as sm

ring.set_prf_impl("threefry")
rk.set_enabled(True)
rk._STATE[("msb", 128)] = "ok"
width, shape = 128, (128, 4150)
vmap_draw = ring.sample_bit_words_seeded


def one_draw(shape_, seeds):
    key = ring._key_from_seed(ring._bit_domain_seed(seeds[0]))
    return jax.random.bits(key, (len(seeds),) + tuple(shape_), dtype=jnp.uint32)


def stack_draw(shape_, seeds):
    return jnp.stack([
        jax.random.bits(ring._key_from_seed(ring._bit_domain_seed(s)), tuple(shape_), dtype=jnp.uint32)
        for s in seeds
    ])


def u64_draw(shape_, seeds):
    # one threefry block gives 64 bits: draw uint64 over half the lanes
    def one(seed):
        key = ring._key_from_seed(ring._bit_domain_seed(seed))
        w = jax.random.bits(key, tuple(shape_[:-1]) + (shape_[-1] // 2,), dtype=jnp.uint64)
        lo = (w & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
        hi = (w >> jnp.uint64(32)).astype(jnp.uint32)
        return jnp.concatenate([lo, hi], axis=-1)
    return jax.vmap(one)(jnp.stack(seeds))


rng = np.random.default_rng(0)
lo = jnp.asarray(rng.integers(0, 1 << 64, size=(3, 2) + shape, dtype=np.uint64))
hi = jnp.asarray(rng.integers(0, 1 << 64, size=(3, 2) + shape, dtype=np.uint64))
mk = jnp.asarray(np.arange(4, dtype=np.uint32) + 9)


def draw_only(mk, lo, hi):
    sess = spmd.SpmdSession(mk)
    b = sm._draw_adder_banks(sess, spmd.SpmdRep(lo, hi, width))
    return jnp.bitwise_xor.reduce(b, axis=(0, 1, 2))  # forces every word, small result


def compare(mk, lo, hi):
    sess = spmd.SpmdSession(mk)
    return sm.msb(sess, spmd.SpmdRep(lo, hi, width)).arr


def timed(fn):
    f = jax.jit(fn)
    for _ in range(3):
        jax.block_until_ready(f(mk, lo, hi))
    t = time.perf_counter()
    for _ in range(20):
        out = f(mk, lo, hi)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / 20 * 1e3


print("device", jax.devices()[0].device_kind, flush=True)
for name, draw in (("vmap", vmap_draw), ("stack", stack_draw), ("one", one_draw), ("u64", u64_draw), ("vmap", vmap_draw)):
    ring.sample_bit_words_seeded = draw
    print(f"{name:6s} draw+reduce {timed(draw_only):8.3f} ms   draw+msb {timed(compare):8.3f} ms", flush=True)
