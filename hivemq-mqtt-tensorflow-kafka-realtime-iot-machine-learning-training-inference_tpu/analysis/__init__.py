"""iotml.analysis — project-wide concurrency & protocol-invariant checker.

The hot paths of this framework — MQTT broker, Kafka wire server/client,
follower replica, group coordinator, stream-proc pump — are hand-rolled
threaded code.  Their pipeline invariants (monotonic timeout clocks,
idempotent-only auto-retry, context-managed locks, no blocking I/O under
a broker lock, engine-owned topic write exclusivity) are machine-checked
here rather than left as tribal knowledge:

- ``lint``       AST lint pass over the tree: rules R1-R17, run via
                 ``python -m iotml.analysis lint`` (exit 1 on findings).
- ``protocol``   whole-program wire-protocol conformance (P1-P7):
                 api-id ↔ handler ↔ encoder ↔ error-code ↔ idempotency
                 tables extracted from the Python server/client, the
                 cluster router, the C++ client, the lint allowlist and
                 the chaos registry, checked for N-way symmetry.
- ``tracecheck`` JAX trace discipline (T1-T4): recompile & host-sync
                 hazards over the jit/scan/shard_map entry points; plus
                 a runtime recompile guard the pytest plugin arms with
                 ``IOTML_TRACECHECK=1`` (a warmed hot loop that
                 re-traces fails its test).
- ``drift``      registry drift (D1-D4): IOTML_* env knobs vs config,
                 metric label sets vs declarations, faultpoint strings
                 vs the chaos registry, rule ids vs ARCHITECTURE rows.
- ``lockorder``  static acquire-order extraction from nested ``with``
                 blocks (per-class call-graph fixpoint) — pre-seeds the
                 runtime cycle detector below.
- ``lockcheck``  runtime lock-order & race detector: an instrumented
                 ``threading.Lock``/``RLock`` wrapper that records the
                 per-thread lock-acquisition graph, fails on cycles
                 (deadlock potential), flags locks held across blocking
                 I/O, and tags unguarded mutations of registered shared
                 state from non-owner threads.  Enable for a pytest run
                 with ``IOTML_LOCKCHECK=1`` or
                 ``-p iotml.analysis.pytest_plugin``.
- the C++ edge is covered by TSan/ASan build targets instead
  (``make -C iotml/cpp sanitize``) — and statically by the protocol
  pass's P4 textual parse of ``cpp/kafka_client.cc``.

All passes share one parse per file (``analysis.program.Program``); the
CLI summary reports wall time and files parsed.

See ARCHITECTURE.md §25 for the rule tables, how to add a rule, and
how to suppress a finding with justification (``# lint-ok: <rule>
<reason>`` covers every family: R*, P*, T*, D*).
"""

from .lint import Finding, RULES, lint_paths  # noqa: F401
from .program import FileUnit, Program  # noqa: F401
