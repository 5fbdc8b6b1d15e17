"""Reference-compatible PRF mode (blake3 + AES-128-CTR).

The reference derives seeds with blake3 and expands them with AES-128-CTR
(``/root/reference/moose/src/host/prim.rs:113-147``,
``host/ops.rs:1959-2040``).  ``set_prf_impl("aes-ctr")`` reproduces that
construction on the host: these tests pin the official BLAKE3 empty-input
vector, the CTR keystream against the FIPS-197-validated AES block, the
reference's draw orders (ring128 = high limb first), and golden values of
the full derive->expand pipeline so any refactor that would break
cross-implementation compatibility fails loudly.

Caveat recorded here rather than hidden: the ``aes_prng`` crate's exact
``get_bit`` consumption granularity (one keystream BYTE per bit is
assumed) could not be verified offline; the u64/u128 uniform paths and
the seed derivation follow the published construction exactly.
"""

import json
import pathlib

import numpy as np
import pytest

from moose_tpu.crypto.aes_prng import AesCtrRng, derive_seed
from moose_tpu.crypto.blake3 import blake3, derive_key, keyed_hash
from moose_tpu.dialects import ring
from moose_tpu.dialects.aes import aes128_encrypt_block_np

# the executable PRF specification: composed-construction vectors
# (stream bytes per (seed, offset), block boundaries, draw orders, bit
# granularity, seed derivation) recorded next to the implementation
GOLDEN = json.loads(
    (pathlib.Path(__file__).resolve().parents[1]
     / "moose_tpu" / "crypto" / "prf_golden.json").read_text()
)


def test_blake3_official_empty_vector():
    assert blake3(b"").hex() == (
        "af1349b9f5f9a1a6a0404dea36dcc949"
        "9bcb25c9adc112b7cc9a93cae41f3262"
    )


def test_blake3_xof_prefix_and_modes():
    assert blake3(b"moose", out_len=64)[:32] == blake3(b"moose")
    key = bytes(range(32))
    assert keyed_hash(key, b"moose") != blake3(b"moose")
    assert derive_key("Derive Seed", b"moose") != blake3(b"moose")
    # multi-block (>64B) and multi-chunk (>1024B) inputs agree with the
    # incremental structure (prefix property of the XOF at the root)
    long = bytes(range(256)) * 20  # 5120 B -> 6 chunks
    assert blake3(long, out_len=64)[:32] == blake3(long)


def test_aes_ctr_keystream_is_counter_mode():
    seed = bytes(range(16))
    rng = AesCtrRng(seed)
    first = rng.next_bytes(16)
    second = rng.next_bytes(16)
    assert first == aes128_encrypt_block_np(
        seed, (0).to_bytes(16, "little")
    )
    assert second == aes128_encrypt_block_np(
        seed, (1).to_bytes(16, "little")
    )


def test_reference_draw_orders():
    seed = bytes(range(16))
    ks = AesCtrRng(seed).next_bytes(32)
    # u64s consume consecutive 8-byte LE words
    u = AesCtrRng(seed).uniform_u64(3)
    assert u[0] == int.from_bytes(ks[0:8], "little")
    assert u[2] == int.from_bytes(ks[16:24], "little")
    # ring128: (hi << 64) + lo with the HIGH limb drawn first
    lo, hi = AesCtrRng(seed).uniform_u128(1)
    assert hi[0] == int.from_bytes(ks[0:8], "little")
    assert lo[0] == int.from_bytes(ks[8:16], "little")


def test_derive_seed_golden():
    """Golden values of the reference construction
    blake3.keyed_hash(blake3.derive_key("Derive Seed", key),
    sid(16) || sync(16))[:16] — pins this implementation across
    refactors; a pymoose cross-check would compare exactly this."""
    key = bytes(range(16))
    seed = derive_seed(key, "sess", bytes(16))
    assert len(seed) == 16
    assert seed == derive_seed(key, "sess", bytes(16))  # deterministic
    assert seed != derive_seed(key, "sess2", bytes(16))
    assert seed != derive_seed(key, "sess", bytes([1]) + bytes(15))
    for vec in GOLDEN["derive_seed"]:
        got = derive_seed(
            bytes.fromhex(vec["key"]), vec["session_id"],
            bytes.fromhex(vec["sync_key"]),
        )
        assert got.hex() == vec["seed"], vec


def test_keystream_bytes_per_seed_and_offset():
    """Exact stream bytes at every recorded (seed, offset) — the
    stream is a pure function of (key, counter) with byte-granular
    positions, so a read after skipping ``offset`` bytes must equal
    the recorded slice regardless of how earlier reads were batched."""
    for vec in GOLDEN["keystream"]:
        rng = AesCtrRng(bytes.fromhex(vec["seed"]))
        if vec["offset"]:
            rng.next_bytes(vec["offset"])
        got = rng.next_bytes(len(vec["bytes"]) // 2)
        assert got.hex() == vec["bytes"], vec
        # split reads concatenate to the same stream (no per-read
        # block realignment)
        rng2 = AesCtrRng(bytes.fromhex(vec["seed"]))
        for _ in range(vec["offset"]):
            rng2.next_bytes(1)
        assert rng2.next_bytes(len(vec["bytes"]) // 2).hex() == vec["bytes"]


def test_keystream_block_boundary():
    """A read straddling the 16-byte block boundary is the suffix of
    block(counter=0) followed by the prefix of block(counter=1) — the
    counter increments little-endian per block with no byte skipped or
    repeated."""
    vec = GOLDEN["block_boundary"]
    seed = bytes.fromhex(vec["seed"])
    b0, b1 = bytes.fromhex(vec["block0"]), bytes.fromhex(vec["block1"])
    assert b0 == aes128_encrypt_block_np(seed, (0).to_bytes(16, "little"))
    assert b1 == aes128_encrypt_block_np(seed, (1).to_bytes(16, "little"))
    off = vec["straddle_offset"]
    straddle = bytes.fromhex(vec["straddle_bytes"])
    assert straddle == (b0 + b1)[off:off + len(straddle)]
    rng = AesCtrRng(seed)
    rng.next_bytes(off)
    assert rng.next_bytes(len(straddle)) == straddle


def test_draw_order_goldens():
    """The composed element orders: u64s are consecutive LE words,
    u128s draw the high limb first, bit draws burn one keystream byte
    per bit (the aes_prng crate's get_bit granularity)."""
    for vec in GOLDEN["u64_draws"]:
        got = AesCtrRng(bytes.fromhex(vec["seed"])).uniform_u64(
            vec["count"]
        )
        assert [f"{v:016x}" for v in got] == vec["values"]
    for vec in GOLDEN["u128_draws"]:
        lo, hi = AesCtrRng(bytes.fromhex(vec["seed"])).uniform_u128(
            vec["count"]
        )
        assert [f"{v:016x}" for v in lo] == vec["lo"]
        assert [f"{v:016x}" for v in hi] == vec["hi"]
    for vec in GOLDEN["bit_draws"]:
        rng = AesCtrRng(bytes.fromhex(vec["seed"]))
        assert list(map(int, rng.bits(vec["count"]))) == vec["bits"]
        # one byte per bit: the stream position after n bit draws is
        # exactly n bytes in
        fresh = AesCtrRng(bytes.fromhex(vec["seed"]))
        fresh.next_bytes(vec["consumed_bytes"])
        assert rng.next_bytes(8) == fresh.next_bytes(8)


def test_bit_domain_tagging():
    """Bit draws flip the top bit of the last u32 seed word before
    touching the cipher (``ring._bit_domain_seed``) — the domain
    separation MSA802 audits: an untagged bit draw would share its
    counter stream with ring draws from the same seed."""
    vec = GOLDEN["bit_domain_tag"]
    words = np.asarray(vec["seed_words"], dtype=np.uint32)
    tagged = np.asarray(ring._bit_domain_seed(words))
    assert tagged.tolist() == vec["tagged_words"]
    assert (
        np.bitwise_xor(words, np.asarray(vec["xor_mask"], np.uint32))
        .tolist() == vec["tagged_words"]
    )
    # tagged and untagged streams are distinct from the first byte
    seed = words.tobytes()
    assert AesCtrRng(seed).next_bytes(16) != AesCtrRng(
        tagged.astype(np.uint32).tobytes()
    ).next_bytes(16)


def test_secure_dot_under_aes_ctr_prf():
    """End-to-end: the whole replicated dot protocol runs with the
    reference PRF construction (eager; aes-ctr is host-side) and reveals
    the right answer; two sessions with the same id and keys are
    bit-identical."""
    import jax

    from moose_tpu.dialects import replicated as rp
    from moose_tpu.execution.session import EagerSession
    from moose_tpu.computation import ReplicatedPlacement
    from moose_tpu.values import HostTensor

    ring.set_prf_impl("aes-ctr")
    try:
        rep = ReplicatedPlacement("rep", ("alice", "bob", "carole"))

        def run():
            sess = EagerSession(
                session_id="prf-fixture",
                master_key=np.frombuffer(bytes(range(16)), np.uint32),
            )
            x = sess.ring_fixedpoint_encode(
                "alice",
                HostTensor(np.array([[1.25, -2.5]]), "alice", None),
                27, 64,
            )
            y = sess.ring_fixedpoint_encode(
                "bob",
                HostTensor(np.array([[0.5], [2.0]]), "bob", None),
                27, 64,
            )
            xs = rp.share(sess, rep, x)
            ys = rp.share(sess, rep, y)
            zs = rp.dot(sess, rep, xs, ys)
            zs = rp.trunc_pr(sess, rep, zs, 27)
            z = rp.reveal(sess, rep, zs, "carole")
            return np.asarray(
                sess.ring_fixedpoint_decode("carole", z, 27).value
            )

        a = run()
        b = run()
        np.testing.assert_array_equal(a, b)  # bit-identical reruns
        np.testing.assert_allclose(a, [[-4.375]], atol=1e-6)
    finally:
        ring.set_prf_impl("rbg")


@pytest.mark.parametrize("impl", ("aes-ctr", "threefry"))
def test_bit_words_carry_a_seeds_own_stream(impl):
    """``sample_bit_words_seeded`` (the fused adder's AND banks, 32 mask
    bits to a uint32): under ``aes-ctr`` bit ``i`` of a seed's tagged
    reference bit stream sits at bit ``i % 32`` of word ``i // 32``;
    under ``threefry`` the draws joined over the seeds are each seed's
    own uint32 draw, under the bit tag, word for word."""
    import jax

    seeds = [
        np.array([1, 2, 3, 4], np.uint32),
        np.array([5, 6, 7, 0x80000008], np.uint32),
    ]
    shape = (3, 2, 8)
    ring.set_prf_impl(impl)
    try:
        words = np.asarray(ring.sample_bit_words_seeded(shape, seeds))
        assert words.shape == (2,) + shape and words.dtype == np.uint32
        for seed, got in zip(seeds, words):
            tagged = ring._bit_domain_seed(seed)
            if impl == "aes-ctr":
                rng = AesCtrRng(np.asarray(tagged, np.uint32).tobytes())
                want = np.packbits(
                    rng.bits(32 * 48), bitorder="little"
                ).view("<u4").reshape(shape)
            else:
                want = np.asarray(jax.random.bits(
                    ring._key_from_seed(tagged), shape, dtype=np.uint32
                ))
            assert np.array_equal(got, want)
        assert not np.array_equal(words[0], words[1])
    finally:
        ring.set_prf_impl("rbg")


def test_aes_ctr_rejects_jit():
    import jax

    from moose_tpu.errors import ConfigurationError

    ring.set_prf_impl("aes-ctr")
    try:
        def f(seed):
            lo, hi = ring.sample_uniform_seeded((2,), seed, 64)
            return lo

        with pytest.raises(ConfigurationError, match="aes-ctr"):
            jax.jit(f)(np.zeros(4, np.uint32))
    finally:
        ring.set_prf_impl("rbg")


def test_distributed_workers_under_aes_ctr_prf():
    """The reference-PRF construction runs across role-filtered workers
    too (workers execute eagerly, so the host-side blake3/AES path
    composes with the real Send/Receive machinery): a 3-worker secure
    dot under aes-ctr reveals the right value."""
    import threading

    import moose_tpu as pm
    from moose_tpu.compilation import DEFAULT_PASSES, compile_computation
    from moose_tpu.compilation.lowering import arg_specs_from_arguments
    from moose_tpu.distributed.networking import LocalNetworking
    from moose_tpu.distributed.worker import execute_role
    from moose_tpu.edsl import tracer

    alice = pm.host_placement("alice")
    bob = pm.host_placement("bob")
    carole = pm.host_placement("carole")
    rep = pm.replicated_placement("rep", players=[alice, bob, carole])

    @pm.computation
    def comp(
        x: pm.Argument(placement=alice, dtype=pm.float64),
        w: pm.Argument(placement=bob, dtype=pm.float64),
    ):
        with alice:
            xf = pm.cast(x, dtype=pm.fixed(14, 23))
        with bob:
            wf = pm.cast(w, dtype=pm.fixed(14, 23))
        with rep:
            y = pm.dot(xf, wf)
        with carole:
            out = pm.cast(y, dtype=pm.float64)
        return out

    rng = np.random.default_rng(9)
    x = rng.normal(size=(2, 3))
    w = rng.normal(size=(3, 1))
    args = {"x": x, "w": w}
    compiled = compile_computation(
        tracer.trace(comp), DEFAULT_PASSES,
        arg_specs=arg_specs_from_arguments(args),
    )

    ring.set_prf_impl("aes-ctr")
    try:
        net = LocalNetworking()
        results, errors = {}, {}

        def work(identity):
            try:
                results[identity] = execute_role(
                    compiled, identity, {}, args, net,
                    session_id="aes-ctr-dist", timeout=60.0,
                )
            except Exception as e:  # surfaced below
                errors[identity] = e

        threads = [
            threading.Thread(target=work, args=(i,), daemon=True)
            for i in ("alice", "bob", "carole")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        outs = {
            k: v for r in results.values()
            for k, v in r["outputs"].items()
        }
        (val,) = outs.values()
        np.testing.assert_allclose(val, x @ w, atol=1e-5)
    finally:
        ring.set_prf_impl("rbg")
