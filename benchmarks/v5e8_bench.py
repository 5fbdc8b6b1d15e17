"""Ready-to-run multi-chip benchmark for a v5e-8 (or any >=3-chip) slice.

One-command config for a multi-chip slice (VERDICT r4 #8); no record
of a run on real chips exists yet (ROADMAP R7):

    python benchmarks/v5e8_bench.py [--batch 4096] [--features 256]

It builds the (parties=3, data=n//3) mesh over the real devices
(`spmd.make_mesh`), runs the chained secure logreg training step and the
chained secure dot with the party/batch axes sharded, and prints one
JSON line per metric (same schema as bench.py).  On a single chip it
degenerates to the unsharded bench (parties co-located), so it can be
smoke-tested anywhere; the numbers become multi-chip evidence exactly
when `jax.devices()` grows.
"""

import argparse
import json
import time

import numpy as np

import moose_tpu  # noqa: F401  (enables x64)
import jax
import jax.numpy as jnp

from moose_tpu.parallel import spmd

I, F, W = 14, 23, 128


def _bench(fn, args, iters=10):
    float(fn(*args))  # compile + warm
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        float(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), float(np.min(times))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--features", type=int, default=256)
    ap.add_argument("--steps", type=int, default=10,
                    help="training steps chained in one program")
    ap.add_argument("--dot-n", type=int, default=1000)
    args = ap.parse_args()

    devices = jax.devices()
    mesh = spmd.make_mesh(len(devices))
    p, d = mesh.devices.shape
    print(f"# devices={len(devices)} mesh=(parties={p}, data={d}) "
          f"backend={jax.default_backend()}")

    rng = np.random.default_rng(0)
    mk = np.arange(4, dtype=np.uint32) + 1
    batch = (args.batch // d) * d or d
    x = rng.normal(size=(batch, args.features)) * 0.3
    y = (rng.uniform(size=(batch, 1)) > 0.5).astype(np.float64)
    w0 = rng.normal(size=(args.features, 1)) * 0.1

    @jax.jit
    def train(master_key, x_f, y_f, w_f):
        sess = spmd.SpmdSession(master_key)
        xs = spmd.fx_encode_share(sess, x_f, I, F, W)
        ys = spmd.fx_encode_share(sess, y_f, I, F, W)
        ws = spmd.fx_encode_share(sess, w_f, I, F, W)
        keys = spmd.derive_step_keys(
            jnp.asarray(master_key, jnp.uint32), args.steps
        )

        def body(wc, k):
            s = spmd.SpmdSession(k)
            return spmd.logreg_train_step(s, xs, ys, wc, 0.1, mesh=mesh), None

        ws, _ = jax.lax.scan(body, ws, keys)
        return jnp.sum(spmd.fx_reveal_decode(ws))

    with mesh:
        med, mn = _bench(train, (mk, x, y, w0))
    print(json.dumps({
        "metric": f"v5e8_logreg_train_step_batch{batch}_f{args.features}",
        "value": med / args.steps, "min_s": mn / args.steps,
        "unit": "s/step", "mesh": [int(p), int(d)],
    }), flush=True)

    a = rng.normal(size=(args.dot_n, args.dot_n))
    b = rng.normal(size=(args.dot_n, args.dot_n))

    @jax.jit
    def dot(master_key, x_f, y_f):
        sess = spmd.SpmdSession(master_key)
        xs = spmd.fx_encode_share(sess, x_f, I, F, W)
        xs = spmd.SpmdFixed(spmd.constrain(xs.tensor, mesh, 0), I, F)
        ys = spmd.fx_encode_share(sess, y_f, I, F, W)
        z = spmd.fx_dot(sess, xs, ys)
        return jnp.sum(spmd.fx_reveal_decode(z))

    with mesh:
        med, mn = _bench(dot, (mk, a, b))
    print(json.dumps({
        "metric": f"v5e8_secure_dot_{args.dot_n}x{args.dot_n}_ring128",
        "value": med, "min_s": mn, "unit": "s",
        "mesh": [int(p), int(d)],
    }), flush=True)


if __name__ == "__main__":
    main()
