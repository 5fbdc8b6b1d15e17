"""The secure dot as a user writes it: ``@pm.computation`` over three
host placements and one replicated placement (the reference benchmark's
``dot_product.py``; the builder is ``chip_smoke.py``'s)."""


def build(pm, config: dict, case: dict, fixed_dtype):
    alice, bob, carole = (pm.host_placement(p) for p in config["parties"])
    rep = pm.replicated_placement("rep", players=[alice, bob, carole])

    @pm.computation
    def secure_dot(
        x: pm.Argument(placement=alice, dtype=pm.float64),
        y: pm.Argument(placement=bob, dtype=pm.float64),
    ):
        with alice:
            xf = pm.cast(x, dtype=fixed_dtype)
        with bob:
            yf = pm.cast(y, dtype=fixed_dtype)
        with rep:
            z = pm.dot(xf, yf)
        with carole:
            return pm.cast(z, dtype=pm.float64)

    return secure_dot
