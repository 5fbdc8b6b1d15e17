"""The device cache's content fingerprint (ISSUE 29): every byte of a
cached argument through a keyed 64-bit hash, read where the array lies
(``form=pieces``) with no temporary that grows with the array; an array
with no flat view of its buffer is copied whole as before
(``form=copied``).  And what ``put`` does with it: hit, miss, stale."""

import tracemalloc

import numpy as np
import pytest

from moose_tpu import metrics, telemetry
from moose_tpu.execution import interpreter
from moose_tpu.execution.interpreter import _FINGERPRINT_PIECE as PIECE
from moose_tpu.execution.interpreter import _DeviceCache

fingerprint = _DeviceCache._fingerprint

SIZES = {
    "under_one_piece": PIECE // 2 + 3,
    "exactly_one_piece": PIECE,
    "one_byte_over_a_piece": PIECE + 1,
    "not_a_multiple": 2 * PIECE + 4099,
    "exactly_three_pieces": 3 * PIECE,
}


def _bytes(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=n, dtype=np.uint8)


@pytest.mark.parametrize("n", SIZES.values(), ids=SIZES.keys())
def test_equal_contents_in_two_arrays_give_equal_fingerprints(n):
    a = _bytes(n)
    b = a.copy()
    assert a.ctypes.data != b.ctypes.data
    assert fingerprint(a) == fingerprint(b)
    assert fingerprint(a)[1] == "pieces"
    assert fingerprint(a) != fingerprint(_bytes(n, seed=1))


FLIPS = {
    "first_byte": 0,
    "last_byte": -1,
    "last_byte_of_the_first_piece": PIECE - 1,
    "first_byte_of_the_second_piece": PIECE,
    "last_byte_of_the_second_piece": 2 * PIECE - 1,
    "first_byte_of_the_last_piece": 2 * PIECE,
}


@pytest.mark.parametrize("at", FLIPS.values(), ids=FLIPS.keys())
@pytest.mark.parametrize("bit", [0, 7])
def test_one_flipped_bit_changes_the_fingerprint(at, bit):
    a = _bytes(SIZES["not_a_multiple"])
    before = fingerprint(a)
    a[at] ^= np.uint8(1 << bit)
    after = fingerprint(a)
    assert after[0] != before[0]
    a[at] ^= np.uint8(1 << bit)
    assert fingerprint(a) == before


def test_a_float64_matrix_is_read_as_its_bytes():
    """The cells' arguments: the flat view is the buffer itself, and a
    change of one element's last mantissa bit shows."""
    a = np.random.default_rng(2).normal(size=(300, 700))
    view = interpreter._flat_bytes(a)
    assert view.base is not None and np.shares_memory(view, a)
    assert view.size == a.nbytes
    before = fingerprint(a)
    a[150, 350] = np.nextafter(a[150, 350], np.inf)
    assert fingerprint(a)[0] != before[0]


def _c_order(a):
    return a


def _fortran_order(a):
    return np.asfortranarray(a)


def _read_only(a):
    a = a.copy()
    a.flags.writeable = False
    return a


def _matrix_subclass(a):
    return a.copy().view(np.matrix)  # its reshape(-1) is (1, n), not flat


def _strided_slice(a):
    wide = np.zeros((a.shape[0], 2 * a.shape[1]), dtype=a.dtype)
    wide[:, ::2] = a
    return wide[:, ::2]


def _object_array(a):
    return a.astype(object)


LAYOUTS = [
    (_c_order, "pieces"), (_fortran_order, "pieces"), (_read_only, "pieces"),
    (_matrix_subclass, "pieces"),
    (_strided_slice, "copied"), (_object_array, "copied"),
]


@pytest.mark.parametrize(
    "make,form", LAYOUTS, ids=[make.__name__[1:] for make, _ in LAYOUTS]
)
def test_every_layout_is_fingerprinted_exactly(make, form):
    """Whatever road the array's flags and dtype send it down: equal
    contents agree, one changed element does not.  (An object array's
    bytes are its elements' addresses, as they were: the same objects
    agree, a replaced one does not.)"""
    base = np.random.default_rng(3).normal(size=(384, 512))  # 1.5 MiB
    a = make(base)
    b = a.copy() if a.dtype.hasobject else make(base.copy())
    assert not np.shares_memory(a, b)
    assert fingerprint(a) == fingerprint(b)
    assert fingerprint(a)[1] == form
    changed = base.copy()
    changed[-1, -1] += 1.0
    assert fingerprint(make(changed))[0] != fingerprint(a)[0]
    if a.dtype.hasobject:  # one replaced object among the same others
        b[-1, -1] = b[-1, -1] + 1.0
        assert fingerprint(b)[0] != fingerprint(a)[0]


def test_fortran_order_is_hashed_in_memory_order():
    """An F-contiguous array is its transpose's C buffer: the same
    bytes in the same order, so the same fingerprint, and no copy."""
    a = np.asfortranarray(np.random.default_rng(4).normal(size=(200, 300)))
    assert np.shares_memory(interpreter._flat_bytes(a), a)
    assert fingerprint(a) == fingerprint(np.ascontiguousarray(a.T))


def test_no_temporary_grows_with_the_array():
    """NumPy and ``bytes`` both report to ``tracemalloc``: the old
    ``hash(arr.tobytes())`` peaks at the array's own 32 MiB."""
    a = _bytes(32 << 20)
    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        base, _ = tracemalloc.get_traced_memory()
        _, form = fingerprint(a)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert form == "pieces"
    assert peak - base < 4 << 20


def _counter(name):
    return dict(metrics.REGISTRY.snapshot().get(name, {}).get("values", {}))


def _put(cache, arr):
    """``put`` under a root span: the device array, the spans it
    recorded, and the counters it moved."""
    names = ("moose_tpu_device_cache_lookups_total",
             "moose_tpu_input_fingerprint_total",
             "moose_tpu_host_device_bytes_total")
    before = [_counter(n) for n in names]
    with telemetry.span("root") as root:
        out = cache.put(arr)
    moved = {}
    for was, name in zip(before, names):
        for key, value in _counter(name).items():
            if value != was.get(key, 0):
                moved[key] = value - was.get(key, 0)
    return out, [(s.name, s.attrs) for s in root.children], moved


def test_put_hits_misses_and_re_uploads_a_mutated_array():
    cache = _DeviceCache()
    w = np.random.default_rng(5).normal(size=(128, 128))  # 128 KiB
    n = w.nbytes
    fp_span = ("input_fingerprint", {"bytes": n, "form": "pieces"})

    first, spans, moved = _put(cache, w)
    assert spans == [fp_span, ("input_upload", {"bytes": n, "why": "miss"})]
    assert moved == {"result=miss": 1, "form=pieces": 1,
                     "direction=hashed": n, "direction=h2d": n}

    again, spans, moved = _put(cache, w)
    assert again is first
    assert spans == [fp_span]
    assert moved == {"result=hit": 1, "form=pieces": 1, "direction=hashed": n}

    w[:] = np.random.default_rng(6).normal(size=w.shape)
    fresh, spans, moved = _put(cache, w)
    assert spans == [fp_span, ("input_upload", {"bytes": n, "why": "stale"})]
    assert moved == {"result=stale": 1, "form=pieces": 1,
                     "direction=hashed": n, "direction=h2d": n}
    assert fresh is not first
    np.testing.assert_array_equal(np.asarray(fresh), w)


def test_put_counts_a_strided_argument_as_copied():
    cache = _DeviceCache()
    view = np.random.default_rng(7).normal(size=(256, 256))[:, ::2]
    assert not view.flags.c_contiguous and not view.flags.f_contiguous
    out, spans, moved = _put(cache, view)
    assert spans[0] == ("input_fingerprint", {"bytes": view.nbytes, "form": "copied"})
    assert moved["form=copied"] == 1 and moved["result=miss"] == 1
    np.testing.assert_array_equal(np.asarray(out), view)
    _, spans, moved = _put(cache, view)
    assert [name for name, _ in spans] == ["input_fingerprint"]
    assert moved["result=hit"] == 1
