"""The benchmark's own tests, run by hand from the root of the repo:
``JAX_PLATFORMS=cpu python -m pytest chipbench/tests`` (about 30 s).
They are not under ``tests/`` and the tier-1 command does not collect
them."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
