"""A float64 result leaves a TPU as its two float32 halves (ISSUE 27):
the pair ``_stage_user_value`` / ``_fetch_user_value`` of
``execution/interpreter.py`` with ``values.Float64Halves`` between
them.  On the CPU backend the path is the one it was (``direct``); the
split and the join are driven here with a stand-in for the platform
test, and on a TPU (one test, skipped elsewhere) against ``np.asarray``
of the same device array."""

import jax
import numpy as np
import pytest

from moose_tpu import dtypes as dt
from moose_tpu import metrics, telemetry, values
from moose_tpu.execution import interpreter as interp
from moose_tpu.runtime import LocalMooseRuntime
from test_span_tree import N, PARTIES, _secure_dot  # 96 x 96 float64 = 72 KiB


def _bits(a):
    return np.ascontiguousarray(a).view(np.uint64)


def _fetch_counts():
    snap = metrics.REGISTRY.snapshot().get("moose_tpu_result_fetch_total", {})
    got = snap.get("values", {})
    return {form: got.get(f"form={form}", 0) for form in ("halves", "direct")}


def _tensor(array):
    return values.HostTensor(jax.numpy.asarray(array), "carole", dt.float64)


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Stand in for the platform test: every device array 'lives on a
    TPU', so the CPU backend runs the split and the join."""
    monkeypatch.setattr(
        interp, "_lives_on_tpu", lambda arr: isinstance(arr, jax.Array)
    )


def _cases(rng, n=N * N):
    """Float64 of at most 48 significant bits, by class."""
    def fixed(integral, fractional, bits=48):
        # a decode of fixed(integral, fractional): k * 2^-fractional
        top = min(integral + fractional, bits) - 1
        k = rng.integers(-(2 ** top), 2 ** top, size=n)
        return k.astype(np.float64) / 2.0 ** fractional

    def cycle(*members):
        return np.resize(np.array(members, dtype=np.float64), n)

    return {
        "fixed_14_23": fixed(14, 23),
        "fixed_24_40": fixed(24, 40),
        "negative": -np.abs(fixed(14, 23)) - 2.0 ** -23,
        "wide_exponents": fixed(24, 24) * 2.0 ** rng.integers(-30, 60, size=n),
        "plus_and_minus_zero": cycle(0.0, -0.0, 1.5, -1.5),
        "only_plus_zero": cycle(0.0, 1.5, -1.5),
        "inf_and_nan": cycle(np.inf, -np.inf, np.nan, 2.0 ** -20, 0.0),
    }


CASES = sorted(_cases(np.random.default_rng(0), 8))


def test_join_is_one_float64_pass():
    rng = np.random.default_rng(1)
    hi = rng.normal(size=(N, N)).astype(np.float32)
    lo = (rng.normal(size=(N, N)) * 2.0 ** -25).astype(np.float32)
    staged = values.Float64Halves(hi, lo, np.bool_(True), whole=None)
    out = values.to_numpy(staged)
    assert out.dtype == np.float64 and out.shape == (N, N)
    assert out.flags.c_contiguous and out.flags.owndata and out.flags.writeable
    np.testing.assert_array_equal(
        _bits(out), _bits(hi.astype(np.float64) + lo.astype(np.float64))
    )


def test_whole_is_no_leaf_of_the_staged_value():
    """``block_until_ready`` and the prefetch see the halves and the
    device's word, not the float64 they stand for."""
    whole = _tensor(np.ones((N, N)))
    staged = values.Float64Halves(*interp._split_float64(whole.value), whole=whole)
    leaves = jax.tree_util.tree_leaves(staged)
    assert [str(leaf.dtype) for leaf in leaves] == ["float32", "float32", "bool"]


@pytest.mark.parametrize("name", CASES)
def test_split_then_join_returns_the_same_bits(as_on_tpu, name):
    want = _cases(np.random.default_rng(2))[name].reshape(N, N)
    staged = interp._stage_user_value(_tensor(want))
    assert isinstance(staged, values.Float64Halves)
    got = interp._fetch_user_value(staged)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    # a -0.0 is the one class here that the halves do not carry
    assert staged.joined == (name != "plus_and_minus_zero")


@pytest.mark.parametrize("name", CASES)
def test_halves_alone_give_the_value(name):
    """The split itself, without the fall-back: everything but the sign
    of a zero, and nan/inf as nan/inf."""
    want = _cases(np.random.default_rng(3))[name]
    hi, lo, _ = interp._split_float64(jax.numpy.asarray(want))
    got = np.add(np.asarray(hi), np.asarray(lo), dtype=np.float64)
    np.testing.assert_array_equal(got, want)  # nan == nan, -0.0 == 0.0 here
    nonzero = want != 0
    np.testing.assert_array_equal(_bits(got[nonzero]), _bits(want[nonzero]))


def test_elements_the_halves_may_not_carry_fetch_the_float64(as_on_tpu):
    tiny = np.full((N, N), 1.0)
    tiny[3, 4] = 1.2345678901234567e-25  # under 2^-64
    before = _fetch_counts()
    staged = interp._stage_user_value(_tensor(tiny))
    assert isinstance(staged, values.Float64Halves) and not staged.joined
    np.testing.assert_array_equal(_bits(interp._fetch_user_value(staged)), _bits(tiny))
    after = _fetch_counts()
    assert after["direct"] == before["direct"] + 1
    assert after["halves"] == before["halves"]


def test_under_64_kib_is_direct_whatever_the_platform(as_on_tpu):
    small = _tensor(np.random.default_rng(4).normal(size=(64, 64)))  # 32 KiB
    assert interp._stage_user_value(small) is small
    before = _fetch_counts()
    out = interp._to_user_value(small)
    after = _fetch_counts()
    np.testing.assert_array_equal(_bits(out), _bits(np.asarray(small.value)))
    assert (after["direct"], after["halves"]) == (
        before["direct"] + 1, before["halves"],
    )


@pytest.mark.parametrize("dtype", ["float32", "int64", "uint64"])
def test_other_dtypes_are_left_alone(as_on_tpu, dtype):
    """64-bit integers keep ``np.asarray`` on purpose (ISSUE 27)."""
    array = jax.numpy.asarray(np.arange(256 * 256).reshape(256, 256), dtype=dtype)
    tensor = values.HostTensor(array, "carole", dt.from_numpy(np.dtype(dtype)))
    assert interp._stage_user_value(tensor) is tensor


def test_host_numpy_values_are_left_alone(as_on_tpu):
    tensor = values.HostTensor(np.ones((N, N)), "carole", dt.float64)
    assert interp._stage_user_value(tensor) is tensor


def test_a_fixed_output_decodes_and_takes_the_same_road(as_on_tpu):
    from moose_tpu.dialects import host

    want = _cases(np.random.default_rng(5))["fixed_14_23"].reshape(N, N)
    want = np.where(want == 0, 1.0, want)
    fixed = host.fixedpoint_encode(_tensor(want), 14, 23, 128, "carole")
    before = _fetch_counts()
    staged = interp._stage_user_value(fixed)
    assert isinstance(staged, values.Float64Halves) and staged.joined
    assert interp._stage_user_value(staged) is staged  # staging is idempotent
    np.testing.assert_array_equal(_bits(interp._to_user_value(fixed)), _bits(want))
    assert _fetch_counts()["halves"] == before["halves"] + 1


def test_the_end_of_an_evaluation_prefetches_what_it_reads(as_on_tpu, monkeypatch):
    """Not the float64 (its copy is the runtime's join, on the runtime's
    thread), and not a fixed output's ring planes either."""
    started = []
    monkeypatch.setattr(
        interp, "prefetch_to_host",
        lambda *trees: started.extend(jax.tree_util.tree_leaves(trees)),
    )
    big, small = _tensor(np.ones((N, N))), _tensor(np.ones((8, 8)))
    ring = values.HostRingTensor(
        jax.numpy.zeros((N, N), "uint64"), None, 64, "alice"
    )
    names, staged, staged_saves = interp.stage_results(
        {"output_1": small, "output_0": big}, {("alice", "k"): ring},
    )
    assert names == ["output_0", "output_1"]
    assert isinstance(staged[0], values.Float64Halves) and staged[1] is small
    assert staged_saves == {("alice", "k"): ring}
    assert sorted(str(leaf.dtype) for leaf in started) == [
        "bool", "float32", "float32", "float64", "uint64",
    ]
    assert all(leaf.nbytes < (1 << 16) for leaf in started if leaf.dtype == np.float64)

    del started[:]
    interp.prefetch_unstaged({"o": big, "p": small}, {("alice", "k"): ring})
    assert sorted(str(leaf.dtype) for leaf in started) == ["float64", "uint64"]
    assert all(leaf.nbytes < (1 << 16) for leaf in started if leaf.dtype == np.float64)


def _evaluate_recording(monkeypatch, arguments, evaluations):
    """Evaluate the secure dot, keeping what each result was staged from
    beside what the user received."""
    raw = []
    stage = interp._stage_user_value

    def recording(value):
        raw.append(value)
        return stage(value)

    monkeypatch.setattr(interp, "_stage_user_value", recording)
    runtime, comp = LocalMooseRuntime(PARTIES), _secure_dot()
    pairs = []
    for _ in range(evaluations):
        (out,) = runtime.evaluate_computation(comp, arguments=arguments).values()
        (value,) = raw
        raw.clear()
        pairs.append((out, value, telemetry.last_trace().find("host_transfer")))
    return pairs


def test_an_evaluation_on_the_cpu_backend_is_direct_and_unchanged(monkeypatch):
    rng = np.random.default_rng(6)
    arguments = {"x": rng.normal(size=(N, N)), "y": rng.normal(size=(N, N))}
    before = _fetch_counts()
    pairs = _evaluate_recording(monkeypatch, arguments, 2)
    after = _fetch_counts()
    assert after == {"halves": before["halves"], "direct": before["direct"] + 2}
    for out, value, span in pairs:
        # the bits np.asarray of the plan's own output gives: today's path
        np.testing.assert_array_equal(_bits(out), _bits(np.asarray(value.value)))
        assert span.attrs["halves"] == 0
    np.testing.assert_allclose(out, arguments["x"] @ arguments["y"], atol=1e-3)


def test_an_evaluation_joins_halves_once_where_the_platform_says_so(
    as_on_tpu, monkeypatch,
):
    rng = np.random.default_rng(7)
    arguments = {"x": rng.normal(size=(N, N)), "y": rng.normal(size=(N, N))}
    before = _fetch_counts()
    ((out, value, span),) = _evaluate_recording(monkeypatch, arguments, 1)
    after = _fetch_counts()
    assert after == {"halves": before["halves"] + 1, "direct": before["direct"]}
    assert span.attrs == {"outputs": 1, "saves": 0, "bytes": N * N * 8, "halves": 1}
    np.testing.assert_array_equal(_bits(out), _bits(np.asarray(value.value)))
    assert out.flags.writeable and out.flags.owndata


def test_on_a_tpu_the_halves_are_np_asarrays_bits(monkeypatch):
    """Point 3 of ISSUE 27 at a small size; ``scripts/chip_fetch_identity.py``
    is the same check at ``dot-2048``'s size under 128 keys."""
    if jax.devices()[0].platform != "tpu":
        pytest.skip("no TPU here: the CPU backend holds a float64 natively")
    rng = np.random.default_rng(8)
    plain = {
        "wide": rng.normal(size=(N, N)) * 2.0 ** rng.integers(-40, 120, size=(N, N)),
        "inf_nan_zero": np.resize(
            np.array([0.0, np.inf, -np.inf, np.nan, 1.0, -1.0]), (N, N)
        ),
        "tiny": rng.normal(size=(N, N)) * 2.0 ** rng.integers(-110, -80, size=(N, N)),
        "minus_zero": np.resize(np.array([-0.0, 0.0, 1.0]), (N, N)),
    }
    for name, host in plain.items():
        device_array = jax.device_put(host)
        staged = interp._stage_user_value(
            values.HostTensor(device_array, "carole", dt.float64)
        )
        assert isinstance(staged, values.Float64Halves), name
        assert staged.joined == (name in ("wide", "inf_nan_zero")), name
        np.testing.assert_array_equal(
            _bits(interp._fetch_user_value(staged)), _bits(np.asarray(device_array)),
            err_msg=name,
        )
    arguments = {"x": rng.normal(size=(N, N)), "y": rng.normal(size=(N, N))}
    for out, value, span in _evaluate_recording(monkeypatch, arguments, 4):
        assert span.attrs["halves"] == 1
        np.testing.assert_array_equal(_bits(out), _bits(np.asarray(value.value)))
