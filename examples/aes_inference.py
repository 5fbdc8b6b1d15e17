"""Encrypted-input inference: the client AES-GCM-encrypts its features;
the 3 compute parties decrypt *under MPC* (the plaintext never exists on
any single machine) and score an ONNX model (reference AesWrapper,
pymoose/pymoose/predictors/predictor.py:49-85).

  python examples/aes_inference.py          # fused local simulation
  python examples/aes_inference.py --grpc   # 3 real worker processes:
      # the ciphertext lowers through the compile pipeline and the AES
      # circuit executes role-filtered over gRPC (slow: the decrypt
      # circuit is ~200k host ops walked eagerly per worker)
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import numpy as np

import moose_tpu as pm
from moose_tpu.dialects import aes
from moose_tpu.runtime import LocalMooseRuntime

alice = pm.host_placement("alice")
bob = pm.host_placement("bob")
carole = pm.host_placement("carole")
rep = pm.replicated_placement("rep", players=[alice, bob, carole])

FIXED = pm.fixed(14, 23)


@pm.computation
def secure_score(
    aes_data: pm.Argument(placement=alice,
                          vtype=pm.AesTensorType(dtype=FIXED)),
    aes_key: pm.Argument(placement=rep, vtype=pm.AesKeyType()),
    w: pm.Argument(placement=bob, dtype=pm.float64),
):
    with rep:
        x = pm.decrypt(aes_key, aes_data)  # AES-128 evaluated on shares
    with bob:
        wf = pm.cast(w, dtype=FIXED)
    with rep:
        score = pm.sigmoid(pm.dot(x, wf))
    with carole:
        out = pm.cast(score, dtype=pm.float64)
    return out


def main():
    rng = np.random.default_rng(1)
    grpc_mode = "--grpc" in sys.argv
    shape = (1, 2) if grpc_mode else (2, 4)
    features = rng.normal(size=shape)
    w = rng.normal(size=(shape[1], 1))

    # the data owner encrypts client-side with any AES-GCM implementation
    key = bytes(range(16))
    nonce = bytes([7] * 12)
    wire = aes.encrypt_fixed_array(key, nonce, features, frac_precision=23)
    arguments = {
        "aes_data": np.asarray(wire),
        "aes_key": np.asarray(aes.bytes_to_bits_be(key)),
        "w": w,
    }

    if grpc_mode:
        import os

        os.environ.setdefault("MOOSE_TPU_PRF", "threefry")
        from moose_tpu.dialects import ring

        ring.set_prf_impl("threefry")  # real share masks between workers
        from moose_tpu.distributed.choreography import (
            spawn_local_workers,
            stop_local_workers,
        )
        from moose_tpu.runtime import GrpcMooseRuntime

        procs, endpoints = spawn_local_workers(22500)
        try:
            runtime = GrpcMooseRuntime(endpoints)
            outputs, timings = runtime.evaluate_computation(
                secure_score, arguments, timeout=900.0
            )
            (scores,) = outputs.values()
            print("per-role micros:", timings)
        finally:
            stop_local_workers(procs)
    else:
        # party-stacked layout: the AES-GCM circuit evaluates as SpmdBits
        # banks and the whole decrypt+score program jits into one XLA
        # program (dialects/aes.py StackedBitOps) — seconds instead of
        # the per-host eager walk
        import time

        runtime = LocalMooseRuntime(
            ["alice", "bob", "carole"], layout="stacked", use_jit=True
        )
        t0 = time.perf_counter()
        (scores,) = runtime.evaluate_computation(
            secure_score, arguments
        ).values()
        t_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        (scores,) = runtime.evaluate_computation(
            secure_score, arguments
        ).values()
        print(
            f"decrypt+score: first call {t_first:.1f}s (compile), "
            f"steady {time.perf_counter() - t0:.2f}s"
        )
    plain = 1 / (1 + np.exp(-(features @ w)))
    print("secure scores:   ", np.ravel(scores))
    print("plaintext scores:", np.ravel(plain))
    assert np.abs(scores - plain).max() < 5e-3
    print("OK")


if __name__ == "__main__":
    main()
