"""The main path's kernels compiled by the chip's own compiler, without
the chip (``on-chip-measurement`` guide §2, third rehearsal): every ring
kernel of ``native/ring128_kernels.py`` at real widths, the tiled dot at
the reference's 1000^3 and at the logreg shape, and the hand-written
logreg forward as one whole program — each for a *described* v5e and
each required to contain a ``tpu_custom_call``.  Interpret mode
(``tests/test_ring128_kernels.py``) cannot see what Mosaic refuses: i64
block indices, an unsupported cast, a tile plan past scoped VMEM.
Nothing runs here, so nothing below says anything about results or time.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every xdist
worker imports this file.  Keep these tests in this one file."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import moose_tpu  # noqa: F401  (x64 setup)
from moose_tpu.dialects import ring
from moose_tpu.native import ring128_kernels as rk

WIDTHS = (64, 128)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def mosaic(monkeypatch, no_persistent_cache):
    """The kernels pick interpret mode from ``jax.default_backend()``,
    which is the CPU here; steer them to real Mosaic lowering."""
    monkeypatch.setattr(rk, "_interpret", lambda: False)


def _compile(fn, one_chip, *specs):
    """``specs`` are (shape, dtype) leaves or None; returns the compiled
    program's text."""
    args = [
        None if s is None
        else jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
        for s in specs
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


def _mosaic_call_named(kernel, text):
    """The Mosaic custom call as an instruction named after its kernel
    (``pallas_call(name=...)``): the name a device trace shows."""
    return re.search(
        rf'%{kernel}[.\d]* = [^\n]*custom_call_target="tpu_custom_call"', text
    )


def _ring(shape, width):
    """(lo, hi) spec pair of a ring tensor; hi is None at ring64."""
    u64 = (shape, jnp.uint64)
    return [u64, u64 if width == 128 else None]


def _pairs(flat):
    return [tuple(flat[i:i + 2]) for i in range(0, len(flat), 2)]


def _ring_mul(width):
    shape = (3, 2, 1000, 1000)  # a stacked replicated tensor
    return (
        lambda *a: rk.ring_mul(*a, width),
        _ring(shape, width) * 2,
    )


def _cross_terms_mul(width):
    shape = (3, 1000, 1000)
    return (
        lambda *a: rk.cross_terms_mul(*_pairs(a), width),
        _ring(shape, width) * 4,
    )


def _trunc_combine(width):
    shape = (1000, 1000)
    amount = 23 if width == 128 else 17

    def fn(*a):
        a0, a1, *draws = _pairs(a)
        return rk.trunc_combine(a0, a1, tuple(draws), width, amount, shape)

    return fn, _ring(shape, width) * 7


def _bits(kernel):
    def case(width):
        shape = (128, 1)
        banks = (
            (rk.adder_bank_count(width), 3)
            + rk.bank_words_shape(width, 128), jnp.uint32
        )
        return (
            lambda lo, hi, b: kernel(lo, hi, width, b),
            _ring((3, 2) + shape, width) + [banks],
        )

    return case


def _horner(width):
    # the degree the fixed-point sigmoid/exp ladders reach at this width
    shape = (128, 1)
    f = 23 if width == 128 else 17
    coeffs = (1.0, 0.7, -0.21, 0.043, -0.0081, 0.0013)
    raws = [
        int(round(c * (1 << f))) % (1 << width) for c in reversed(coeffs)
    ]
    steps = len(raws) - 1

    def fn(*a):
        x0, x1, zb, td = _pairs(a)
        return rk.horner(x0, x1, width, raws, f, zb, td, shape)

    return fn, (
        _ring((3,) + shape, width) * 2
        + _ring((steps, 3) + shape, width)
        + _ring((steps, 5) + shape, width)
    )


KERNELS = {
    "ring_mul": _ring_mul,
    "cross_terms_mul": _cross_terms_mul,
    "trunc_combine": _trunc_combine,
    "msb": _bits(rk.msb),
    "bit_decompose": _bits(rk.bit_decompose),
    "horner": _horner,
}


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(mosaic, one_chip, kernel, width):
    fn, specs = KERNELS[kernel](width)
    assert _mosaic_call_named(kernel, _compile(fn, one_chip, *specs))


def test_msb_compiles_at_a_forests_width_for_v5e(
    mosaic, one_chip, monkeypatch
):
    """One comparison per inner node per row of the boosted forest
    (`gbt-score-batch`): 128 rows x 4150 nodes, a width that is no
    multiple of the kernel's lane block, the AND banks drawn under
    ``threefry`` as the words the kernel reads.  If the kernel declined
    there, its XLA twin would run, the one the ladder pins.  The
    program's temporaries are the 0.41 GB of words and little else: the
    parent's 3.26 GB of bytes, their stack and their packing are gone
    (PERF.md, PR 33), and no uint8 array of a bank's size is left."""
    from moose_tpu.parallel import spmd, spmd_math as sm

    width, shape = 128, (128, 4150)
    monkeypatch.setattr(rk, "_OVERRIDE", True)
    monkeypatch.setitem(rk._STATE, ("msb", width), "ok")  # no first-use run
    prf = ring.get_prf_impl()
    ring.set_prf_impl("threefry")

    def compare(mk, lo, hi):
        sess = spmd.SpmdSession(mk)
        return sm.msb(sess, spmd.SpmdRep(lo, hi, width)).arr

    try:
        compiled = jax.jit(compare).lower(
            jax.ShapeDtypeStruct((4,), jnp.uint32, sharding=one_chip),
            *(
                jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in _ring((3, 2) + shape, width)
            ),
        ).compile()
    finally:
        ring.set_prf_impl(prf)
    text = compiled.as_text()
    assert _mosaic_call_named("msb", text)
    words = 4 * rk.adder_bank_count(width) * 3 * 4 * 4152 * 128
    assert words == 408_158_208
    assert compiled.memory_analysis().temp_size_in_bytes < 1.25 * words
    largest_u8 = max(
        int(np.prod([int(d) for d in dims.split(",")]))
        for dims in re.findall(r"u8\[([\d,]+)\]", text)
    )
    assert largest_u8 <= 3 * 2 * 4152 * 128  # the top plane, padded


def _pallas_call_names(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_call_names(sub, found)
    return found


def _dot_cross_terms(width):
    def fn(*a):
        x0, x1, y0, ys = _pairs(a)
        return rk.dot_cross_terms(x0, x1, y0, ys, width)

    return fn, _ring((3, 128, 100), width) * 2 + _ring((3, 100, 1), width) * 2


@pytest.mark.parametrize("kernel", sorted(KERNELS) + ["dot_cross_terms"])
def test_pallas_call_carries_its_kernels_name(kernel):
    """Traced only (no topology, nothing compiled): every ``pallas_call``
    equation is named as ``ring128_kernels.report()`` and the dispatch
    counters spell the kernel, so a trace can tell the tiled dot from
    ``trunc_combine``."""
    fn, specs = {**KERNELS, "dot_cross_terms": _dot_cross_terms}[kernel](128)
    args = [None if s is None else jax.ShapeDtypeStruct(*s) for s in specs]
    names = _pallas_call_names(jax.make_jaxpr(fn)(*args).jaxpr, [])
    assert names and set(names) == {kernel}
    assert kernel in rk._CHECKS


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize(
    "m,k,n", [(1000, 1000, 1000), (128, 100, 1)],
    ids=["reference-dot", "logreg"],
)
def test_dot_cross_terms_compiles_for_v5e(mosaic, one_chip, m, k, n, width):
    """The default tile plan must fit scoped VMEM (16 MiB on v5e): the
    pre-PR-22 search left the f32 limb temporaries out and Mosaic refused
    ring128 1000^3 by 10 MB."""

    def fn(*a):
        x0, x1, y0, ys = _pairs(a)
        return rk.dot_cross_terms(x0, x1, y0, ys, width)

    specs = _ring((3, m, k), width) * 2 + _ring((3, k, n), width) * 2
    assert _mosaic_call_named("dot_cross_terms", _compile(fn, one_chip, *specs))


def test_logreg_forward_compiles_whole_for_v5e(
    mosaic, one_chip, monkeypatch
):
    """share -> secure dot -> TruncPr -> polynomial sigmoid -> reveal at
    the reference's logreg width (batch 128, 100 features, ring128), one
    XLA program with the ring kernels inside."""
    import __graft_entry__ as entry

    # what the program picks by itself on a TPU backend: the int8 MXU
    # limb matmul (XLA:TPU has no u64 dot) and the kernels on, with the
    # first-use checks taken as passed — they would run the kernels, and
    # nothing runs on a described device
    monkeypatch.setattr(ring, "_MATMUL_STRATEGY", "limb_int8")
    monkeypatch.setattr(rk, "_OVERRIDE", True)
    monkeypatch.setattr(
        rk, "_STATE", {(kernel, entry.W): "ok" for kernel in rk._CHECKS}
    )
    text = _compile(
        entry._forward, one_chip,
        ((4,), jnp.uint32), ((128, 100), jnp.float64),
        ((100, 1), jnp.float64),
    )
    # seven today: the dot's truncation, then the sigmoid polynomial's
    # two secure multiplies (cross terms + truncation) and the
    # truncations of its two public multiplies (whose ring_mul kernel is
    # switched off by name)
    assert text.count("tpu_custom_call") >= 7


def test_dot_tile_plans_fit_the_vmem_budget():
    """Whatever the search returns, its own accounting fits — including
    the contraction split into k segments when one pass cannot."""
    for width in WIDTHS:
        for m, k, n in (
            (1000, 1000, 1000), (128, 100, 1), (512, 512, 128),
            (1024, 128, 8), (100, 2048, 1), (4096, 4096, 4096),
        ):
            bm, bn, kseg = rk._dot_tile_plan(m, k, n, width)
            kp = -(-kseg // 128) * 128
            assert rk._dot_vmem_bytes(bm, bn, kp, width) <= (
                rk._DOT_VMEM_BUDGET
            ), (width, m, k, n)
    # the plan Mosaic refused before this accounting existed
    assert rk._dot_vmem_bytes(32, 128, 1024, 128) > 16 << 20
