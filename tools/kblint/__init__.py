"""kblint: project-invariant static analysis for kubebrain-tpu.

Two tiers (see docs/static_analysis.md for the full catalogue):

**Syntactic** (per-file AST rules, always on):

- KB101  no blocking calls inside ``async def`` bodies (endpoint/, server/)
- KB102  no JAX dispatch / RPC / sleeps while holding a ``threading.Lock``
- KB103  no bare ``except:``
- KB104  no host synchronization inside ``@jax.jit`` kernels (ops/)
- KB105  revision arithmetic must flow through server/service/revision.py
- KB106  service-layer range reads go through the request scheduler
- KB107  no print()/raw time.time() latency math on the serving path
- KB108  TTL/deadline arithmetic only via kubebrain_tpu/lease/clock.py
- KB109  scan kernels dispatch only from the _dev_mask assembly points
- KB110  workload/ stays replayable (no unseeded RNG, no time.time())
- KB111  storage/tpu/ device→host pulls only at named materialization points
- KB116  encoded-key decode only through the decoded_keys/user_key funnels,
  themselves only from the named materialization/rebuild paths
- KB117  query-bound packing/encoding only inside the domain-dispatch
  funnels — kernels never see a bound from the wrong key domain

**Interprocedural** (``--deep``: whole-program call graph + context
propagation over kubebrain_tpu/ + tools/; graph.py/contexts.py):

- KB112  blocking call *transitively* reachable while a lock is held
- KB113  host sync *transitively* reachable from jit/shard_map-traced code
- KB114  device-array taint escaping to host outside the KB111 allowlist
  (catches alias/wrapper laundering the name-based KB111 misses by design)
- KB115  static lock-acquisition-order graph must be acyclic (cross-checked
  against util/lockcheck.py's runtime-observed edges)

Pre-existing deep findings are pinned in tools/kblint/baseline.json, not
silenced; per-file results are cached content-hash-keyed in .kblint_cache/.

Suppress a finding with a trailing comment on the flagged line (or on the
enclosing ``with``/``def`` header for syntactic block rules)::

    subprocess.Popen(...)  # kblint: disable=KB101 -- one-shot startup fork

Run as ``python -m tools.kblint [paths...] [--deep]``.
"""

from .core import (Baseline, Finding, Rule, RULES, deep_analyze_paths,
                   deep_analyze_sources, lint_paths, lint_source, register)

__all__ = ["Baseline", "Finding", "Rule", "RULES", "deep_analyze_paths",
           "deep_analyze_sources", "lint_paths", "lint_source", "register"]
