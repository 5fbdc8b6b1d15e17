import os

# Tests run on a virtual 12-device CPU mesh so multi-chip sharding paths
# are exercised without TPU hardware.  The platform is chosen in the
# environment, as the driver's own command does (JAX_PLATFORMS=cpu); a
# bare ``pytest`` gets the same default here, before jax is imported, so
# subprocesses the tests start inherit it too.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Default to eager per-op execution in tests (reference SyncSession
# behavior): whole-computation XLA compiles are exercised by dedicated
# jit tests.
os.environ.setdefault("MOOSE_TPU_JIT", "0")

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    # 12 virtual devices: enough for party-axis meshes of {3, 6, 8, 12}
    # (test_spmd.py) while still exercising the v5e-8 shape via
    # make_mesh(8).
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=12"
    ).strip()

from moose_tpu import compile_cache  # noqa: E402

# Persistent XLA compilation cache: the jit-parametrized acceptance tests
# compile large protocol graphs; caching across test runs keeps warm
# suites fast.  (Cold compiles are bounded by segmented jit — big graphs
# auto-route through lowering and compile as MOOSE_TPU_JIT_SEGMENT-sized
# XLA programs, each of which caches here independently.)
compile_cache.enable()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running end-to-end tests (MPC AES, full "
        "predictor pipelines); deselect with -m 'not slow'"
    )


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def no_plan_verdict_store(monkeypatch):
    """The suite shares one compile cache directory across runs; a
    ladder verdict kept there by one run would be adopted by the next,
    and tests that watch a plan validate would see it start promoted.
    So tests run as a process without a verdict store does;
    ``test_plan_verdicts.py`` gives its runners a store under
    ``tmp_path``."""
    monkeypatch.setattr(compile_cache, "plan_verdict_dir", lambda: None)


@pytest.fixture
def assert_lints_clean():
    """Assert a computation graph has no static-analysis findings at or
    above a severity (default: error).  Usage::

        def test_my_graph(assert_lints_clean):
            assert_lints_clean(comp)                       # no errors
            assert_lints_clean(comp, fail_on="warning")    # stricter
            assert_lints_clean(comp, ignore=("MSA4",))     # skip hygiene
    """
    from moose_tpu.compilation.analysis import (
        Severity,
        analyze,
        format_diagnostics,
    )

    def check(comp, analyses=None, ignore=(), fail_on="error"):
        threshold = (
            fail_on if isinstance(fail_on, Severity)
            else Severity.from_str(fail_on)
        )
        diagnostics = analyze(comp, analyses=analyses, ignore=ignore)
        failing = [d for d in diagnostics if d.severity >= threshold]
        assert not failing, (
            "graph does not lint clean:\n" + format_diagnostics(failing)
        )
        return diagnostics

    return check
