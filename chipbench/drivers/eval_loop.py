"""One caller, evaluations back to back, through the entry a user calls:
``LocalMooseRuntime(parties).evaluate_computation(comp, arguments=...)``
with the default layout, NumPy float64 in, NumPy float64 out, a fresh
master key per evaluation as the runtime draws it.

The harness (``chipbench/run.py``) calls, in this order: ``setup`` (data
from the seed, the computation, evaluations until the plan has settled
and one more), ``window`` (the timed evaluations), ``release`` and
``check`` (the plain reference over every answer the window kept).  The
same ``evaluate`` call serves set-up and window, so what is timed is what
was warmed and what is compared.
"""

import time

import numpy as np

from chipbench.files import load_module

SETTLE_MAX_EVALS = 12  # the ladder promotes after two clean comparisons


def make_case(ctx) -> dict:
    """The cell's data from the seed, by the configuration's reference
    module: NumPy on the host, nothing of the program."""
    reference = load_module("reference", ctx.config["reference"])
    return reference.make_case(
        ctx.config, ctx.size, ctx.traffic["distinct_inputs"], ctx.seed
    )


def fixed_dtype(pm, config: dict):
    integral, fractional = config["fixed"]
    make = {64: pm.fixed64, 128: pm.fixed128}[config["ring"]]
    return make(integral, fractional)


class State:
    """What set-up builds and the window drives: one object, not two
    built alike."""

    def __init__(self, ctx, case, runtime, comp):
        self.ctx = ctx
        self.case = case  # its "inputs": one argument dict per distinct input
        self.runtime = runtime
        self.comp = comp
        self.setup_evals = 0
        self.setup_plan_states = []
        self.setup_phases = {}  # seconds, for PERF.md's set-up accounting

    def evaluate(self, i: int) -> np.ndarray:
        """Evaluation ``i`` of the cycle, ended when its result is a
        NumPy array on the host."""
        inputs = self.case["inputs"]
        (out,) = self.runtime.evaluate_computation(
            self.comp, arguments=inputs[i % len(inputs)]
        ).values()
        return np.asarray(out)


def setup(ctx) -> State:
    import moose_tpu as pm
    from moose_tpu.dialects import ring
    from moose_tpu.runtime import LocalMooseRuntime

    config = ctx.config
    # the one guarantee the configuration states that is not the
    # program's default; applied before the first trace
    ring.set_prf_impl(config["prf"])
    # the deployment's persisted autotune measurements, where its file
    # has them: without a row the program times its dot kernel against
    # XLA in every process, on the host's clock, and the two are close
    # enough for the choice, and with it the program, to flip from run
    # to run (PERF.md, PR 25 finding 2)
    from moose_tpu.compilation import autotune

    autotune.measurements().load(config.get("autotune_measurements", {}))
    t0 = time.perf_counter()
    case = make_case(ctx)
    t1 = time.perf_counter()
    builder = load_module("computations", config["computation"])
    comp = builder.build(pm, config, case, fixed_dtype(pm, config))
    state = State(ctx, case, LocalMooseRuntime(list(config["parties"])), comp)
    state.setup_phases = {
        "data_s": t1 - t0, "build_s": time.perf_counter() - t1, "evals_s": [],
    }
    # evaluations until the ladder has settled, and one more: its
    # validating runs (jit against eager) are set-up, not window
    settled = False
    for i in range(SETTLE_MAX_EVALS):
        t0 = time.perf_counter()
        state.evaluate(i)
        state.setup_phases["evals_s"].append(time.perf_counter() - t0)
        state.setup_evals += 1
        plan_state = state.runtime.last_plan.get("plan_state")
        state.setup_plan_states.append(plan_state)
        if settled:
            break
        settled = plan_state != "validating"
    else:
        raise SystemExit(
            f"chipbench: plan still {plan_state!r} after "
            f"{SETTLE_MAX_EVALS} set-up evaluations"
        )
    return state


def reseed(state: State, seed: int) -> None:
    """Fresh inputs from another seed for the same computation (the
    readings tool, ``chipbench/control.py``: a dozen seeds, one set-up)."""
    state.ctx.seed = seed
    state.case = make_case(state.ctx)


class Window:
    def __init__(self):
        self.t_open = 0.0
        self.t_close = 0.0
        self.starts = []  # perf_counter at each call
        self.ends = []  # perf_counter at each NumPy result
        self.kept = []  # (evaluation index, result) for the comparison
        self.errors = []  # (evaluation index, text) of calls that raised

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


def window(state: State, seconds: float, max_evals=None, annotate=None):
    """Evaluations back to back until ``seconds`` have passed (or
    ``max_evals`` are done, in a traced run).  Every answer is kept for
    the comparison up to the mix's ``keep_at_most``; beyond it a sample
    drawn from the seed stays (reservoir), so host memory is bounded
    however fast the program gets."""
    import contextlib

    keep_at_most = state.ctx.traffic["keep_at_most"]
    rng = np.random.default_rng(state.ctx.seed)
    span = annotate or (lambda name, **kw: contextlib.nullcontext())
    rec = Window()
    # the cycle goes on from where set-up stopped
    offset = state.setup_evals
    rec.t_open = time.perf_counter()
    deadline = rec.t_open + seconds
    n = 0
    while True:
        t0 = time.perf_counter()
        if t0 >= deadline or (max_evals is not None and n >= max_evals):
            break
        rec.starts.append(t0)
        try:
            with span("chipbench.evaluate", n=n):
                out = state.evaluate(offset + n)
        except Exception as e:  # noqa: BLE001 — counted in `failed`
            out = None
            rec.errors.append((n, f"{type(e).__name__}: {e}"[:300]))
        rec.ends.append(time.perf_counter())
        with span("chipbench.keep", n=n):
            if out is not None:
                if len(rec.kept) < keep_at_most:
                    rec.kept.append((n, out))
                else:
                    j = int(rng.integers(0, n + 1))
                    if j < keep_at_most:
                        rec.kept[j] = (n, out)
        n += 1
    rec.t_close = time.perf_counter()
    return rec


def end_to_end(rec: Window) -> dict:
    """All the evaluations over all the time of the window; the tail is
    the tail of all of them."""
    times_ms = (np.asarray(rec.ends) - np.asarray(rec.starts)) * 1e3
    done = len(rec.ends) - len(rec.errors)
    return {
        "evals_per_s": done / rec.seconds,
        "eval_p90_ms": float(np.percentile(times_ms, 90)),
    }


def release(state: State) -> None:
    """Drop the program's state before the reference runs."""
    state.runtime = None
    state.comp = None


def check(state: State, rec: Window, produce=None) -> dict:
    """Every answer the window kept against the plain reference of its
    input, each number against the configuration's limit.  ``produce``
    puts something else in the program's place (the control)."""
    config, case = state.ctx.config, state.case
    reference = load_module("reference", config["reference"])
    limits = config["limits"]
    distinct = len(case["inputs"])
    wanted = {}
    worst = {name: 0.0 for name in limits}
    failed = len(rec.errors)
    outside = []
    for n, got in rec.kept:
        i = (state.setup_evals + n) % distinct
        if i not in wanted:
            wanted[i] = reference.expected(config, case, i)
        want = wanted[i]
        if produce is not None:
            got = produce(config, case, i)
        if got.shape != want.shape or not np.isfinite(got).all():
            failed += 1
            outside.append((n, "shape or non-finite"))
            continue
        numbers = reference.numbers(config, case, i, got, want)
        bad = [k for k in limits if not numbers[k] <= limits[k]]
        for k in limits:
            worst[k] = max(worst[k], numbers[k])
        if bad:
            failed += 1
            outside.append((n, {k: numbers[k] for k in bad}))
    compared = {
        name: {"value": worst[name], "limit": limits[name]} for name in limits
    }
    return {
        "attempted": len(rec.ends),
        "compared": len(rec.kept),
        "failed": failed,
        "correct": failed == 0 and len(rec.kept) > 0,
        "numbers": compared,
        "outside": outside[:8],
    }
