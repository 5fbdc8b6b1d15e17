"""Programs set-up compiled and wrote to the persistent cache
(``cache_misses``: JAX counts one where it writes an entry, so a
compile under the cache's floor of a second is none): 0 from the
record; above 0, the run's ``setup_s`` holds a compile."""

from chipbench import setup_spans

NAME = "setup_cache_misses"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "plan + compile cache"
MOVES = "setup_s"


def read(view):
    return setup_spans.row_s(view, "cache_misses")
