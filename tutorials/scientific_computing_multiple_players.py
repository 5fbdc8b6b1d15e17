"""Use case: scientific computing with multiple players.

Executable-doc port of the reference tutorial
``/root/reference/tutorials/scientific-computing-multiple-players.ipynb``:
two government departments each hold a private column of data (alcohol
consumption, student grades); a data scientist wants the Pearson
correlation between them WITHOUT any party revealing its column.  The
whole statistic — means, centered products, the variance product, its
square root, and the final division — is computed on secret-shared
values under 3-party replicated secret sharing; only the single
correlation coefficient is revealed.

Run locally (one process simulating all parties):

    python tutorials/scientific_computing_multiple_players.py

Run across three real worker processes over gRPC (the reference's comet
deployment; workers are spawned for you):

    python tutorials/scientific_computing_multiple_players.py --grpc
"""

import argparse

import pathlib as _pathlib
import sys as _sys

_sys.path.insert(0, str(_pathlib.Path(__file__).resolve().parents[1]))

import numpy as np

import moose_tpu as pm

FIXED = pm.fixed(24, 40)

# One placement per real-world party.  The replicated placement is the
# "virtual encrypted machine" spanned by the three of them: values that
# move onto it are secret-shared, computation on it runs on shares.
pub_health_dpt = pm.host_placement(name="pub_health_dpt")
education_dpt = pm.host_placement(name="education_dpt")
data_scientist = pm.host_placement(name="data_scientist")
encrypted_government = pm.replicated_placement(
    name="encrypted_government",
    players=[pub_health_dpt, education_dpt, data_scientist],
)


def generate_synthetic_correlated_data(n_samples):
    """Synthetic (alcohol, grades) columns with a known anticorrelation
    (same construction as the reference tutorial)."""
    mu = np.array([10.0, 0.0])
    r = np.array([[3.40, -2.75], [-2.75, 5.50]])
    rng = np.random.default_rng(12)
    x = rng.multivariate_normal(mu, r, size=n_samples)
    return x[:, 0:1], x[:, 1:2]


def pearson_correlation_coefficient(x, y):
    """corr = sum((x-mx)(y-my)) / sqrt(sum((x-mx)^2) * sum((y-my)^2)),
    every op below runs on secret shares (sqrt is the secure
    2^(log2/2) protocol, div the Goldschmidt protocol)."""
    x_mean = pm.mean(x, 0)
    y_mean = pm.mean(y, 0)
    stdv_x = pm.sum(pm.square(pm.sub(x, x_mean)))
    stdv_y = pm.sum(pm.square(pm.sub(y, y_mean)))
    corr_num = pm.sum(pm.mul(pm.sub(x, x_mean), pm.sub(y, y_mean)))
    corr_denom = pm.sqrt(pm.mul(stdv_x, stdv_y))
    return pm.div(corr_num, corr_denom)


@pm.computation
def multiparty_correlation():
    # Each department loads ITS OWN data from ITS OWN storage, in
    # plaintext, then casts to the fixed-point encoding the protocol
    # computes over.
    with pub_health_dpt:
        alcohol = pm.load("alcohol_data", dtype=pm.float64)
        alcohol = pm.cast(alcohol, dtype=FIXED)

    with education_dpt:
        grades = pm.load("grades_data", dtype=pm.float64)
        grades = pm.cast(grades, dtype=FIXED)

    # Crossing from a host placement into the replicated placement
    # secret-shares the values; nothing in this block ever exists in
    # the clear on any single machine.
    with encrypted_government:
        correlation = pearson_correlation_coefficient(alcohol, grades)

    # Only the final scalar is revealed, and only to the data scientist.
    with data_scientist:
        correlation = pm.cast(correlation, dtype=pm.float64)
        correlation = pm.save("correlation", correlation)

    return correlation


def run_local(alcohol, grades):
    from moose_tpu.runtime import LocalMooseRuntime

    runtime = LocalMooseRuntime(
        identities=["pub_health_dpt", "education_dpt", "data_scientist"],
        storage_mapping={
            "pub_health_dpt": {"alcohol_data": alcohol},
            "education_dpt": {"grades_data": grades},
        },
    )
    runtime.set_default()
    multiparty_correlation()
    return np.asarray(
        runtime.read_value_from_storage("data_scientist", "correlation")
    )


def run_grpc(alcohol, grades, base_port=23500):
    """The same computation across three real worker processes over gRPC
    — the reference's `comet` deployment shape.  Workers are spawned
    here for convenience; in a real deployment each party runs its own.
    """
    from moose_tpu.dialects import ring
    from moose_tpu.distributed.choreography import (
        spawn_local_workers,
        stop_local_workers,
    )

    ring.set_prf_impl("threefry")  # real share masks between workers
    procs, endpoints = spawn_local_workers(base_port)
    try:
        from moose_tpu.runtime import GrpcMooseRuntime

        runtime = GrpcMooseRuntime(endpoints)
        runtime.set_default()
        # workers hold no storage here, so feed the columns as inputs
        alice, bob, carole = (
            pm.host_placement("alice"),
            pm.host_placement("bob"),
            pm.host_placement("carole"),
        )
        rep = pm.replicated_placement("rep", players=[alice, bob, carole])

        @pm.computation
        def corr_inputs(
            a: pm.Argument(placement=alice, dtype=pm.float64),
            g: pm.Argument(placement=bob, dtype=pm.float64),
        ):
            with alice:
                af = pm.cast(a, dtype=FIXED)
            with bob:
                gf = pm.cast(g, dtype=FIXED)
            with rep:
                c = pearson_correlation_coefficient(af, gf)
            with carole:
                out = pm.cast(c, dtype=pm.float64)
            return out

        outputs, _timings = runtime.evaluate_computation(
            corr_inputs, {"a": alcohol, "g": grades}
        )
        (val,) = outputs.values()
        return np.asarray(val)
    finally:
        stop_local_workers(procs)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--grpc", action="store_true",
                        help="run across 3 spawned gRPC workers")
    parser.add_argument("--samples", type=int, default=100)
    args = parser.parse_args(argv)

    alcohol, grades = generate_synthetic_correlated_data(args.samples)
    if args.grpc:
        moose_corr = run_grpc(alcohol, grades)
    else:
        moose_corr = run_local(alcohol, grades)

    np_corr = np.corrcoef(alcohol.ravel(), grades.ravel())[1, 0]
    print(f"Correlation with moose_tpu: {float(np.ravel(moose_corr)[0]):.6f}")
    print(f"Correlation with numpy:     {np_corr:.6f}")
    assert abs(float(np.ravel(moose_corr)[0]) - np_corr) < 1e-2
    print("OK — secure result matches the plaintext statistic")
    return float(np.ravel(moose_corr)[0])


if __name__ == "__main__":
    main()
