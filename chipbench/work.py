"""The operations and bytes a cell's protocol needs, from the
configuration's shapes and ring width alone, whatever implements them.

A ring multiply-add at 8-bit limbs (the MXU's int8 path) needs the limb
pairs (i, j) whose product reaches below bit ``ring``: i + j < ring / 8,
which is 136 pairs at ring128 and 36 at ring64; a multiply and an add
each.  A replicated dot is ``ring_matmuls`` (6) ring matmuls of m.k.n
multiply-adds; a replicated elementwise multiplication is the same with
k = 1; a replicated AND gate is 6 one-bit ANDs.  The per-row counts of a
nonlinear layer are in the configuration's ``work`` block, with their
derivation in ``PERF.md``.
"""

import json
import os

LIMB_BITS = 8
_HERE = os.path.dirname(os.path.abspath(__file__))


def limb_pairs(ring: int) -> int:
    limbs = ring // LIMB_BITS
    return limbs * (limbs + 1) // 2


def _resolve(dims, config: dict, size: dict) -> list:
    names = {**{k: v for k, v in config["shapes"].items()
                if isinstance(v, int)}, **size}
    return [d if isinstance(d, int) else names[d] for d in dims]


def dot_shape(config: dict, size: dict) -> tuple:
    return tuple(_resolve(config["work"]["dot"], config, size))


def ring_ops(config: dict, size: dict) -> float:
    """int8 operations of one evaluation's ring work."""
    work, ring = config["work"], config["ring"]
    m, k, n = dot_shape(config, size)
    per_mac = limb_pairs(ring) * 2
    ops = work["ring_matmuls"] * m * k * n * per_mac
    ops += m * work.get("secure_mul_per_row", 0) * work["ring_matmuls"] * per_mac
    ops += m * work.get("and_gates_per_row", 0) * work["ring_matmuls"]
    return float(ops)


def ring_bytes(config: dict, size: dict) -> float:
    """Bytes the same work has to move through the chip's memory at the
    least: each ring matmul reads two operands and writes one result,
    and ``elementwise_ring_passes`` more passes go over the result."""
    work, ring = config["work"], config["ring"]
    m, k, n = dot_shape(config, size)
    element = ring // 8
    matmul = work["ring_matmuls"] * (m * k + k * n + m * n) * element
    passes = work.get("elementwise_ring_passes", 0) * m * n * element
    return float(matmul + passes)


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in chipbench/peaks.json "
            f"(known: {sorted(table)}): add its published peaks with their "
            "source"
        )
    return table[device_kind]


def least_seconds(config: dict, size: dict, device_kind: str) -> tuple:
    """(seconds, which peak bounds) for one evaluation's ring work."""
    peak = peaks(device_kind)
    by_ops = ring_ops(config, size) / peak["int8_ops_per_s"]
    by_bytes = ring_bytes(config, size) / peak["hbm_bytes_per_s"]
    return (by_ops, "int8") if by_ops >= by_bytes else (by_bytes, "hbm")
