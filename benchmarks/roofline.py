"""Per-phase roofline breakdown of the headline secure dot.

Answers "where do the milliseconds go" for the party-stacked secure
matmul (``spmd.fx_dot``).  A single call pays a *serialized per-call*
dispatch floor that says nothing about the chip (scripts/peak_probe.py
times it: where the floor is milliseconds, a 1000^3 and a 4096^3 matmul
take the same time per call).  Every number here is therefore measured
as T iterations chained
*inside one jitted program* via ``lax.scan`` (carry-fed so nothing can
be hoisted out of the loop), with one scalar readback at the end —
amortized per-iteration time approximates true device time.

Phases (matching replicated/arith.rs:317-454 + additive/trunc.rs):
  encode+share   fixed-point encode + PRF share of both operands
  cross-products regrouped local contractions x_i(y_i+y_{i+1}) + x_{i+1}y_i
  reshare        zero-share bank draw + add + pair roll
  trunc_pr       probabilistic truncation (mask, reveal c, recombine)
  reveal+decode  share sum + fixed-point decode

Run: python benchmarks/roofline.py [N] [T]
Prints one JSON line with per-phase amortized ms and an MFU estimate
against this chip's *achievable* int8 matmul rate (scripts/peak_probe.py
measures ~113 TOP/s at 8192^3 through this harness).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import moose_tpu  # noqa: F401
import jax
import jax.numpy as jnp

from moose_tpu import compile_cache
from moose_tpu.dialects import ring
from moose_tpu.parallel import spmd

compile_cache.enable()

I, F, W = 14, 23, 128

# measured achievable dense int8 rate on this chip+harness (peak_probe)
ACHIEVABLE_INT8_OPS = 113e12


def _chain_time(make_body, init_carry, t_iters, reps=3):
    """Amortized per-iteration seconds of body chained under lax.scan in
    ONE jit call; the carry threads through every iteration so the loop
    body cannot be hoisted, and the final scalar readback forces true
    execution of the asynchronous dispatch."""

    @jax.jit
    def run():
        c, _ = jax.lax.scan(
            make_body, init_carry, None, length=t_iters
        )
        leaves = jax.tree_util.tree_leaves(c)
        return sum(jnp.sum(x).astype(jnp.float64) for x in leaves)

    float(run())  # compile + warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        s = run()
        float(s)
        times.append(time.perf_counter() - t0)
    # subtract nothing: one dispatch amortized over t_iters is noise
    return float(np.min(times)) / t_iters


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1000
    t_iters = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    rng = np.random.default_rng(3)
    a = rng.normal(size=(n, n))
    b = rng.normal(size=(n, n))
    mk = np.frombuffer(b"moose-tpu-bench!", dtype=np.uint32)

    da, db = jax.device_put(a), jax.device_put(b)

    def fresh_sess(c):
        # fold the loop carry into the master key: each iteration draws a
        # distinct PRF stream AND the scan body stays carry-dependent
        return spmd.SpmdSession(
            jnp.asarray(mk, jnp.uint32) ^ c.astype(jnp.uint32)
        )

    # --- materialized intermediates for phase isolation ---
    @jax.jit
    def stage(x_f, y_f):
        sess = spmd.SpmdSession(mk)
        xs = spmd.fx_encode_share(sess, x_f, I, F, W)
        ys = spmd.fx_encode_share(sess, y_f, I, F, W)
        v_lo, v_hi = spmd._cross_terms(xs.tensor, ys.tensor, _contract)
        z = spmd._reshare(sess, v_lo, v_hi, W)
        zt = spmd.trunc_pr(sess, z, F)
        return xs, ys, v_lo, v_hi, z, zt

    def _contract(a_lo, a_hi, b_lo, b_hi):
        f = jax.vmap(lambda p, ph, q, qh: ring.matmul(p, ph, q, qh))
        return f(a_lo, a_hi, b_lo, b_hi)

    xs, ys, v_lo, v_hi, z, zt = jax.block_until_ready(stage(da, db))

    def inject(rep, c):
        # carry-dependence without changing cost class: one cheap xor
        lo = rep.lo ^ c
        return spmd.SpmdRep(lo, rep.hi, rep.width)

    c0 = jnp.uint64(0)

    def body_share(c, _):
        sess = fresh_sess(c)
        xs_ = spmd.fx_encode_share(sess, da + c.astype(jnp.float64) * 0, I, F, W)
        ys_ = spmd.fx_encode_share(sess, db, I, F, W)
        return xs_.tensor.lo[0, 0, 0, 0] + ys_.tensor.lo[0, 0, 0, 0], None

    def body_cross(c, _):
        xt = inject(xs.tensor, c)
        v_lo_, v_hi_ = spmd._cross_terms(xt, ys.tensor, _contract)
        return v_lo_[0, 0, 0], None

    def body_reshare(c, _):
        sess = fresh_sess(c)
        z_ = spmd._reshare(sess, v_lo ^ c, v_hi, W)
        return z_.lo[0, 0, 0, 0], None

    def body_trunc(c, _):
        sess = fresh_sess(c)
        zt_ = spmd.trunc_pr(sess, inject(z, c), F)
        return zt_.lo[0, 0, 0, 0], None

    def body_reveal(c, _):
        out = ring.fixedpoint_decode(*spmd.reveal(inject(zt, c)), F)
        return c + jnp.sum(out).astype(jnp.uint64), None

    def body_full(c_rep, _):
        # carry the FULL output tensor (a scalar carry would let XLA
        # dead-code-eliminate work not feeding it, flattering the number)
        sess = fresh_sess(c_rep.lo[0, 0, 0, 0])
        z_ = spmd.fx_dot(
            sess, spmd.SpmdFixed(c_rep, I, F),
            spmd.SpmdFixed(ys.tensor, I, F),
        )
        return z_.tensor, None

    phases = {
        "share_ms": _chain_time(body_share, c0, t_iters),
        "cross_products_ms": _chain_time(body_cross, c0, t_iters),
        "reshare_ms": _chain_time(body_reshare, c0, t_iters),
        "trunc_pr_ms": _chain_time(body_trunc, c0, t_iters),
        "reveal_decode_ms": _chain_time(body_reveal, c0, t_iters),
        "full_chained_ms": _chain_time(body_full, xs.tensor, t_iters),
    }
    phases = {k: round(v * 1e3, 3) for k, v in phases.items()}

    # sanity: full secure dot still correct end to end
    @jax.jit
    def full(x_f, y_f):
        sess = spmd.SpmdSession(mk)
        xs_ = spmd.fx_encode_share(sess, x_f, I, F, W)
        ys_ = spmd.fx_encode_share(sess, y_f, I, F, W)
        zz = spmd.fx_dot(sess, xs_, ys_)
        return spmd.fx_reveal_decode(zz)

    out = np.asarray(full(da, db))
    err = np.abs(out - a @ b).max()
    assert err < 2e-4, f"secure dot mismatch: {err}"

    # MFU estimate for the cross-product phase: the regrouped secure dot
    # does 2 contractions x 3 parties; each u128 limb_int8 matmul is 136
    # s8xs8->s32 (n, n, n)-MAC slabs (pairs i+j < 16 of 16 limbs)
    strat = ring.get_matmul_strategy()
    record = {
        "metric": "secure_dot_phase_breakdown",
        "n": n,
        "t_iters": t_iters,
        "prf": ring.get_prf_impl(),
        "matmul_strategy": strat,
        "int8_diag": os.environ.get("MOOSE_TPU_INT8_DIAG", "pairs"),
        **phases,
        "sum_of_phases_ms": round(
            sum(v for k, v in phases.items() if k != "full_chained_ms"), 3
        ),
    }
    if strat == "limb_int8":
        ops = 2 * 2 * 3 * 136 * n * n * n  # 2 ops/MAC
        t_cross = phases["cross_products_ms"] / 1e3
        record["cross_mxu_ops"] = ops
        record["cross_mfu_vs_achievable_int8"] = round(
            (ops / t_cross) / ACHIEVABLE_INT8_OPS, 3
        )
        record["achievable_int8_roofline_ms"] = round(
            ops / ACHIEVABLE_INT8_OPS * 1e3, 3
        )
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
