"""Device-resident edge protection (ISSUE 17): intercept tap-match +
next-hop route rewrite on the fast path.

- `edge.ops` — the two device kernels (tap_match, route_rewrite) and
  their word layouts, probed via `lookup()`.
- `edge.tables` — `EdgeTables`, the host single-writer authority whose
  bounded deltas ride the engine's existing update drain.
- `edge.compile` — warrant/routing compilers + the `MirrorPump` host
  retire sink that feeds `RecordCC`/HI3 export.
"""

from bng_tpu.edge.compile import (CLASS_CODES, InterceptTapProgram,
                                  MirrorPump, RouteProgram)
from bng_tpu.edge.ops import (EDGE_NSTATS, EST_MIRRORED, EST_ROUTE_MISSES,
                              EST_ROUTE_REWRITES, EST_TAP_FILTERED,
                              ROUTE_WORDS, TAP_WORDS, RouteResult, TapResult,
                              route_rewrite, tap_match)
from bng_tpu.edge.tables import MAX_TAP_FILTERS, MAX_WARRANTS, EdgeTables

__all__ = [
    "CLASS_CODES", "EDGE_NSTATS", "EST_MIRRORED", "EST_ROUTE_MISSES",
    "EST_ROUTE_REWRITES", "EST_TAP_FILTERED", "EdgeTables",
    "InterceptTapProgram", "MAX_TAP_FILTERS", "MAX_WARRANTS", "MirrorPump", "ROUTE_WORDS",
    "RouteProgram", "RouteResult", "TAP_WORDS", "TapResult",
    "route_rewrite", "tap_match",
]
