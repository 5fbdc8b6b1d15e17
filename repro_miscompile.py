"""Standalone repro for the TPU-jit XLA miscompile on large protocol
graphs (DEVELOP.md "Known issue" says on which runtimes it was seen).

Builds the LOWERED secure-softmax computation (~10k host-level integer
ops over ring128) and executes it twice from IDENTICAL PRF keys (the
lowered graph is fully deterministic given its runtime key inputs —
every seed-derivation nonce is a baked graph attribute):

  1. eagerly, op by op (the exact reference — per-op XLA programs are
     measured correct at every size), and
  2. as jitted XLA program(s) of ``--segment`` ops each (0 = the whole
     graph as ONE program),

and reports the max |difference| per segment.  The two paths compute the
same integer math from the same randomness, so ANY difference is a
backend miscompile, not protocol noise.

Expected results:
  - CPU backend: PASS at every segment size.
  - the experimental TPU runtime of rounds 3-6: FAIL for large
    programs (historically: one
    ~500-op window inside exp's b2a/polynomial region diverges with
    err ~5e13; 50-op segments all pass; returning every intermediate as
    an output also passes — an output-set-sensitive whole-program bug,
    not a kernel bug).

Usage:
  python repro_miscompile.py                  # whole graph, equal keys
  python repro_miscompile.py --segment 500    # bisect: per-segment diff
  python repro_miscompile.py --keys random    # value-dependence probe
  python repro_miscompile.py --platform cpu   # control run
  python repro_miscompile.py --xla-bisect     # XLA-flag sweep over the
                                              # stacked fx_sigmoid repro
  python repro_miscompile.py --sigmoid-probe  # one jit-vs-eager sigmoid
                                              # check under current env

Exit code 0 = paths agree (bug not reproduced), 1 = divergence.

``--xla-bisect`` (VERDICT r5 Weak #3) targets the sharpest known
reproducer — a single jitted ``spmd_math.fx_sigmoid`` at fixed(24,40)
diverges from its own eager execution on that TPU runtime — and
sweeps ``--xla_disable_hlo_passes`` / fusion / scheduler toggles
hunting a flag set under which it compiles correctly.  XLA reads
``XLA_FLAGS`` once at backend init, so every configuration probes in a
fresh subprocess (``--sigmoid-probe``).  The baseline probe also dumps
the program's HLO (``--dump-hlo``) — with the sweep summary, that file
IS the sharpened upstream repro when no flag set helps.  Outcomes are
recorded in DEVELOP.md ("Known issue" section).
"""

import argparse
import os
import subprocess
import sys

import numpy as np

# XLA_FLAGS configurations the bisect sweeps, coarsest lever first.
# All use --xla_disable_hlo_passes (present on every backend; unknown
# pass NAMES in the list are ignored, unknown FLAGS would abort), so
# one sweep runs identically on cpu (control) and tpu (the target).
XLA_BISECT_CONFIGS = (
    ("baseline", ""),
    ("no-fusion", "--xla_disable_hlo_passes=fusion"),
    (
        "no-fusion-family",
        "--xla_disable_hlo_passes=fusion,fusion_merger,"
        "multi_output_fusion,horizontal_loop_fusion,"
        "horizontal_input_fusion",
    ),
    ("no-algsimp", "--xla_disable_hlo_passes=algsimp"),
    (
        "no-scheduler",
        "--xla_disable_hlo_passes=latency-hiding-scheduler,"
        "rematerialization",
    ),
    (
        "no-fusion-no-scheduler",
        "--xla_disable_hlo_passes=fusion,fusion_merger,"
        "multi_output_fusion,latency-hiding-scheduler",
    ),
)


def sigmoid_probe(precision, batch: int, dump_hlo=None,
                  pallas: bool = False) -> int:
    """One jit-vs-eager comparison of the stacked protocol sigmoid
    under the CURRENT process environment (XLA_FLAGS already applied).
    The computation is deterministic given the fixed master key, so any
    difference is a miscompile.  Returns the exit code.

    ``pallas=True`` forces the ring128 Pallas kernels on (ISSUE 9): the
    hot primitives become opaque Mosaic programs XLA cannot re-fuse, so
    this probe doubles as the regression guard that the kernel path is
    bit-exact under whole-graph jit — the sidestep for the very
    miscompile this file reproduces."""
    import moose_tpu  # noqa: F401  (x64 + plugin setup)
    import jax

    from moose_tpu.parallel import spmd
    from moose_tpu.parallel import spmd_math as sm

    if pallas:
        from moose_tpu.native import ring128_kernels

        ring128_kernels.set_enabled(True)

    integ, frac = precision
    # Goldschmidt division inside the protocol sigmoid needs
    # 2*(integ+frac) <= ring width
    width = 64 if 2 * (integ + frac) <= 64 else 128
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 4)) * 2.0
    mk = np.arange(4, dtype=np.uint32) + 21

    def forward(master_key, x_f):
        sess = spmd.SpmdSession(master_key)
        xs = spmd.fx_encode_share(sess, x_f, integ, frac, width)
        return spmd.fx_reveal_decode(sm.fx_sigmoid(sess, xs))

    print(f"backend: {jax.default_backend()}  fixed({integ},{frac}) "
          f"ring{width}  XLA_FLAGS={os.environ.get('XLA_FLAGS', '')!r}",
          flush=True)
    if pallas:
        from moose_tpu.native import ring128_kernels

        print(f"pallas kernels: {ring128_kernels.report()}", flush=True)
    eager = np.asarray(forward(mk, x))
    jfn = jax.jit(forward)
    if dump_hlo:
        with open(dump_hlo, "w") as fh:
            fh.write(jfn.lower(mk, x).as_text())
        print(f"HLO written to {dump_hlo}")
    jitted = np.asarray(jfn(mk, x))
    if pallas:
        # guard against a vacuous pass: if every kernel fell back, this
        # probe re-tested the plain XLA path and proves nothing about
        # the Pallas route it exists to guard
        from moose_tpu.native import ring128_kernels

        verdicts = ring128_kernels.report()["kernels"]
        bad = {k: v for k, v in verdicts.items() if v != "ok"}
        if not verdicts or bad:
            print(
                "FAIL: --pallas requested but the kernel path did not "
                f"run cleanly: {bad or 'no kernel dispatched'}"
            )
            return 1
    if np.array_equal(eager, jitted):
        print("PASS: jitted fx_sigmoid bit-identical to eager")
        return 0
    err = float(np.abs(eager - jitted).max())
    print(f"FAIL: jitted fx_sigmoid diverges, max|diff|={err:.3e}")
    return 1


def xla_bisect(precision, batch: int, platform=None) -> int:
    """Sweep XLA_BISECT_CONFIGS over the fx_sigmoid repro in fresh
    subprocesses; print a verdict table and return 0 when either the
    bug does not reproduce (control backend) or a working flag set was
    found, 1 when every configuration diverges (the dumped HLO + this
    table are the upstream repro)."""
    integ, frac = precision
    hlo_path = os.path.abspath(f"fx_sigmoid_fixed{integ}_{frac}.hlo.txt")
    results = []
    for name, flags in XLA_BISECT_CONFIGS:
        env = dict(os.environ)
        base = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = f"{base} {flags}".strip()
        cmd = [
            sys.executable, os.path.abspath(__file__),
            "--sigmoid-probe", "--precision", f"{integ},{frac}",
            "--batch", str(batch),
        ]
        if platform:
            cmd += ["--platform", platform]
        if name == "baseline":
            cmd += ["--dump-hlo", hlo_path]
        print(f"--- {name}: XLA_FLAGS={env['XLA_FLAGS']!r}", flush=True)
        try:
            proc = subprocess.run(
                cmd, env=env, timeout=900, capture_output=True, text=True,
            )
            ok = proc.returncode == 0
            tail = (proc.stdout or proc.stderr).strip().splitlines()
            print("    " + (tail[-1] if tail else "(no output)"))
        except subprocess.TimeoutExpired:
            ok = False
            print("    TIMEOUT (counted as FAIL)")
        results.append((name, ok))

    print("\n=== xla-bisect summary ===")
    for name, ok in results:
        print(f"  {'PASS' if ok else 'FAIL':4}  {name}")
    baseline_ok = results[0][1]
    fixes = [n for n, ok in results[1:] if ok]
    if baseline_ok:
        print("\nbaseline PASSES: the miscompile does not reproduce on "
              "this backend (control run)")
        return 0
    if fixes:
        print(f"\nWORKING FLAG SET(S): {', '.join(fixes)} — record in "
              "DEVELOP.md and consider pinning for worker deployments")
        return 0
    print(f"\nNO flag set fixes the divergence: {hlo_path} plus this "
          "table is the sharpened upstream repro")
    return 1


def build_lowered_softmax(arguments, classes=4, precision=(24, 40)):
    import moose_tpu as pm
    from moose_tpu.compilation import DEFAULT_PASSES, compile_computation
    from moose_tpu.compilation.lowering import arg_specs_from_arguments
    from moose_tpu.edsl import tracer

    alice = pm.host_placement("alice")
    bob = pm.host_placement("bob")
    carole = pm.host_placement("carole")
    rep = pm.replicated_placement("rep", players=[alice, bob, carole])

    @pm.computation
    def comp(x: pm.Argument(placement=alice, dtype=pm.float64)):
        with alice:
            xf = pm.cast(x, dtype=pm.fixed(*precision))
        with rep:
            y = pm.softmax(xf, axis=1, upmost_index=classes)
        with carole:
            out = pm.cast(y, dtype=pm.float64)
        return out

    # local execution: keep the graph unnetworked (no Send/Recv pairs)
    passes = [p for p in DEFAULT_PASSES if p != "networking"]
    return compile_computation(
        tracer.trace(comp), passes,
        arg_specs=arg_specs_from_arguments(arguments),
    )


def main():
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument("--segment", type=int, default=0,
                        help="ops per jitted segment (0 = one program)")
    parser.add_argument("--keys", choices=["equal", "random"],
                        default="equal",
                        help="equal = deterministic repro keys; random = "
                        "fresh keys (failure is value-dependent)")
    parser.add_argument("--platform", default=None,
                        help="force a JAX platform (e.g. cpu) before init")
    parser.add_argument("--batch", type=int, default=4)
    parser.add_argument("--classes", type=int, default=4,
                        help="softmax width (fewer classes = smaller "
                        "graph; CI uses 2 as a reduced regression guard)")
    parser.add_argument("--precision", default="24,40",
                        help="fixed-point 'i,f' — e.g. 8,17 selects the "
                        "64-bit ring for a much smaller lowered graph")
    parser.add_argument("--xla-bisect", action="store_true",
                        help="sweep XLA pass-disable flag sets over the "
                        "jitted fx_sigmoid repro (fresh subprocess per "
                        "config; XLA_FLAGS is read once at init)")
    parser.add_argument("--sigmoid-probe", action="store_true",
                        help="one jit-vs-eager fx_sigmoid check under "
                        "the current XLA_FLAGS (the bisect child mode)")
    parser.add_argument("--dump-hlo", default=None, metavar="PATH",
                        help="with --sigmoid-probe: write the jitted "
                        "program's HLO text to PATH")
    parser.add_argument("--pallas", action="store_true",
                        help="with --sigmoid-probe: force the ring128 "
                        "Pallas kernels on (MOOSE_TPU_PALLAS override) "
                        "— the regression guard for the kernel "
                        "sidestep of this miscompile")
    args = parser.parse_args()
    integ, frac = (int(p) for p in args.precision.split(","))

    if args.platform and (args.sigmoid_probe or args.xla_bisect):
        os.environ["JAX_PLATFORMS"] = args.platform
    if args.sigmoid_probe:
        return sigmoid_probe(
            (integ, frac), args.batch, args.dump_hlo, pallas=args.pallas
        )
    if args.xla_bisect:
        return xla_bisect((integ, frac), args.batch, args.platform)

    import moose_tpu  # noqa: F401  (x64 + plugin setup)
    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    print(f"backend: {jax.default_backend()}")

    from moose_tpu.execution import physical
    from moose_tpu.execution.interpreter import plan_segments

    rng = np.random.default_rng(0)
    x = rng.normal(size=(args.batch, args.classes)) * 2.0
    arguments = {"x": x}
    comp = build_lowered_softmax(
        arguments, classes=args.classes, precision=(integ, frac)
    )

    plan = physical._build_plan(comp, arguments, False)
    order, key_ops, dyn_names, static_env, _ = plan
    n_ops = len(order)
    limit = args.segment if args.segment > 0 else n_ops + 1
    recv_src = physical._recv_sources(comp, order)

    def effective_inputs(n):
        op = comp.operations[n]
        if op.kind == "Receive":
            return [recv_src[op.name]]
        return op.inputs

    chunks, in_names, out_names = plan_segments(
        order, static_env, effective_inputs, limit
    )
    print(f"{n_ops} ops, {len(chunks)} segment(s) of <= {limit}")

    # identical PRF keys for both paths (this is the determinism pin the
    # localization used: the lowered graph has no other entropy source)
    if args.keys == "equal":
        keys = {
            n: np.zeros(4, dtype=np.uint32) + 7 for n in key_ops
        }
    else:
        keys = {n: physical._fresh_key_words() for n in key_ops}
    dyn_all = {n: np.asarray(arguments[n]) for n in dyn_names}
    dyn_set = set(dyn_names)
    key_set = set(key_ops)

    from moose_tpu.execution.session import EagerSession

    def seg_callable(si, names):
        outs = list(out_names[si])

        def seg(ks, dyn, env_in):
            sess = EagerSession()
            env = dict(static_env)
            env.update(env_in)
            outputs, saves = {}, {}
            physical._run_physical_ops(
                sess, comp, names, static_env, env, outputs, saves,
                ks, dyn, recv_src,
            )
            return {n: env[n] for n in outs}, outputs

        return seg

    divergent = []
    env = {}  # lockstep: both paths continue from the REFERENCE values
    for si, names in enumerate(chunks):
        seg = seg_callable(si, names)
        import jax as _jax

        seg_jit = _jax.jit(seg)
        ks_i = {n: keys[n] for n in names if n in key_set}
        dyn_i = {n: dyn_all[n] for n in names if n in dyn_set}
        env_in = {n: env[n] for n in in_names[si]}

        ref_env, ref_out = seg(ks_i, dyn_i, env_in)
        jit_env, jit_out = seg_jit(ks_i, dyn_i, env_in)

        worst = 0.0
        for tree_a, tree_b in ((ref_env, jit_env), (ref_out, jit_out)):
            la = _jax.tree_util.tree_leaves(tree_a)
            lb = _jax.tree_util.tree_leaves(tree_b)
            for a, b in zip(la, lb):
                a = np.asarray(a)
                b = np.asarray(b)
                if not np.array_equal(a, b):
                    d = np.abs(
                        a.astype(np.float64) - b.astype(np.float64)
                    ).max()
                    worst = max(worst, float(d))
        status = "OK " if worst == 0.0 else "DIVERGED"
        lo_idx = sum(len(c) for c in chunks[:si])
        print(
            f"segment {si:4d} ops[{lo_idx}:{lo_idx + len(names)}]"
            f" ({names[0]}..{names[-1]}): {status}"
            + (f" max|diff|={worst:.3e}" if worst else ""),
            flush=True,
        )
        if worst:
            divergent.append((si, worst))
        env.update(ref_env)

    if divergent:
        print(f"\nFAIL: {len(divergent)} divergent segment(s): "
              + ", ".join(f"#{si} (|diff|~{d:.1e})" for si, d in divergent))
        return 1
    print("\nPASS: jitted path bit-identical to eager reference")
    return 0


if __name__ == "__main__":
    sys.exit(main())
