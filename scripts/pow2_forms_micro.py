"""By hand, on the chip (PR 36): ``spmd_math._pow2_positive`` alone, one
jitted program a form of the same 61,440 lanes at ``fixed(24, 40)`` on
ring128 under ``threefry``: flat ``(n,)``, ``(n / 128, 128)`` and
rows x 10, shares in and the answer out.  Milliseconds of six calls by
the host's clock round ``block_until_ready``, after the compile:
``chiprun --chips 1 -- env PYTHONPATH=. python3 scripts/pow2_forms_micro.py``.
PERF.md section 7, 10 has the readings (10.9, 6.6 and 22.4 ms, against
91.8 ms for the same function on ``(n,)`` inside ``mlp-score-batch``'s
program, where XLA lays the bit planes' axis minor)."""
import json, time
import jax, numpy as np
from moose_tpu import compile_cache
from moose_tpu.dialects import ring
from moose_tpu.parallel import spmd, spmd_math as sm

compile_cache.enable()
ring.set_prf_impl("threefry")
I, F, W = 24, 40, 128
vals = np.random.default_rng(7).uniform(0.0, 12.0, size=61440)


def go(mk, xv):
    s = spmd.SpmdSession(mk)
    x = spmd.fx_encode_share(s, xv, I, F, W)
    y = sm._pow2_positive(s, x.tensor, I, F)
    return spmd.fx_reveal_decode(spmd.SpmdFixed(y, I, F))


out = {"device": jax.devices()[0].device_kind}
for name, shape in (
    ("flat", (61440,)), ("n128", (480, 128)), ("rows10", (6144, 10))
):
    f, xv = jax.jit(go), vals.reshape(shape)
    t = time.perf_counter()
    got = np.asarray(f(np.arange(4, dtype=np.uint32) + 3, xv))
    first = time.perf_counter() - t
    times = []
    for i in range(6):
        mk = np.arange(4, dtype=np.uint32) + 11 + i
        t = time.perf_counter()
        jax.block_until_ready(f(mk, xv))
        times.append(time.perf_counter() - t)
    out[name] = {
        "first_s": round(first, 1),
        "ms": [round(1e3 * x, 2) for x in times],
        "max_rel_err": float(np.abs(got / 2.0 ** xv - 1).max()),
    }
    print(name, out[name], flush=True)
print(json.dumps(out))
