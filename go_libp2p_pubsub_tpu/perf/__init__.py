"""perf — the repo's measurement subsystem (profiling, artifacts,
projection, sweeps, regression gating).

Every perf claim in BASELINE.md flows through here as code rather than
ad-hoc scripts + markdown arithmetic:

  * :mod:`.artifacts`  — the versioned, self-describing bench-JSON schema
    (v2: config fingerprint incl. score-weight elision flags) + readers
    that still parse the pre-schema driver wrapper files of rounds 1-5;
  * :mod:`.profile`    — library-ified per-op profiler: runs the
    per-round or phase engine at arbitrary ``(N, r, config)`` shapes and
    returns an attributed op table (the BASELINE.md round-5-style table);
  * :mod:`.stages`     — the engine's stages as ``gs.*`` named scopes,
    the stage map read from a compiled window, and the registry of the
    windows traced in this process;
  * :mod:`.projection` — the v5e-8 projection as tested code composing
    measured shard-round times with the collective-cost model pinned by
    tests/test_collectives.py;
  * :mod:`.sweep`      — declarative ``(config × N × r)`` sweep runner
    (owns the bench workload builder);
  * :mod:`.regress`    — the CPU-feasible regression gate behind
    ``make perf-smoke``.

Modules import jax lazily (inside functions) so CLI entry points can
configure the platform/PRNG first — the same contract bench.py has
always had.
"""

from .artifacts import (  # noqa: F401
    SCHEMA_VERSION,
    BenchRecord,
    dump_record,
    load_bench_artifact,
    load_bench_trajectory,
    load_multichip_artifact,
)
from .projection import Projection, project  # noqa: F401
from .sweep import SweepSpec, build_bench, workload_fingerprint  # noqa: F401
